"""Per-layer metric `measure_ms_per_batch.reddit`: mean length of the
program's `plan.measure` span, the phase-boundary wait of a two-phase batch
(mostly the reduce program's run), in ms; each two-phase batch opens one.
None where the run holds no such span."""


def read(run):
    if run.spans is None:
        return None
    ms = [e - s for s, e, n in run.spans if n == "plan.measure"]
    return sum(ms) / len(ms) * 1e3 if ms else None
