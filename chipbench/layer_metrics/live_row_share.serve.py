"""Share of the step program's rows that carried a live token over the
window: (decode + prefill) / (decode + prefill + dead), from the engine's
own ``stats`` counters.  program_counter."""


def read(cell, window, counters, trace):
    live = counters["decode_rows"] + counters["prefill_rows"]
    total = live + counters["dead_rows"]
    return 100.0 * live / total if total else None
