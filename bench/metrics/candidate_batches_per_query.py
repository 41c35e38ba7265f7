"""Index layer: mean number of candidate batches the sketch hands the
post-filter, over the answers of the window."""


def read(run):
    ok = run.answered()
    return sum(r.candidates for r in ok) / len(ok) if ok else None
