"""Device busy microseconds per arrival of the traced unit: the union of
the device's operation intervals (profiler trace) over the arrivals that
unit served through ``VectorizedFleetSimulator.run``."""


def read(run: dict):
    n = run["counters"].get("traced_arrivals", 0)
    busy = run["breakdown"]["busy_s"]
    if not n or busy <= 0:
        return None
    return busy / n * 1e6
