"""Roofline share of the statements' device time, bandwidth-bound: the
least time the chip needs to read every input column of every
statement completed in the traced slice once (host column bytes over
the peak HBM bytes/s of ``peaks.json``), over the time an operation
really ran on the device."""


def read(obs: dict, spec: dict):
    busy_s = obs["trace"]["busy_s"]
    if busy_s <= 0 or not obs["input_bytes"]:
        return None
    least_s = obs["input_bytes"] / obs["peak"]["hbm_bytes_per_s"]
    return 100.0 * least_s / busy_s
