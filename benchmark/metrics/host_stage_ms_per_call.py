"""Host time the program spends around its device work, per call: the
durations of the program's ``PumiTally.stage`` (validation, packing,
staging) and ``PumiTally.fold`` (readback split, copy-back, telemetry)
spans inside the window, summed, per call. Nothing where the trace has
no such span."""


def read(run):
    tr = run.trace
    iv = list(tr.spans_named("PumiTally.stage")) + list(
        tr.spans_named("PumiTally.fold"))
    calls = run.counts["init"] + run.counts["move"]
    if not iv or not calls:
        return None
    return float(sum(e - s for s, e in iv)) * 1e-6 / calls
