"""Share of the traced unit in which no operation ran on the device, in
percent: host pre-split, trace compilation and lane merge of
``VectorizedFleetSimulator.run`` sit in these gaps."""


def read(run: dict):
    b = run["breakdown"]
    if b["window_s"] <= 0 or b["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - b["busy_s"] / b["window_s"])
