"""Per-layer metric `persist_calls_per_batch.reddit`: `plan.persist` spans
per `plan.measure` span, i.e. how many persist programs a two-phase batch
fans out into.  None where the run holds no two-phase batch."""


def read(run):
    if run.spans is None:
        return None
    names = [n for _, _, n in run.spans]
    batches = names.count("plan.measure")
    return names.count("plan.persist") / batches if batches else None
