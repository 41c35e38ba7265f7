"""Index layer: share (%) of candidate batches that hold no match,
1 - sum(true_batches) / sum(candidate batches) over the window."""


def read(run):
    ok = run.answered()
    cand = sum(r.candidates for r in ok)
    if not cand:
        return None
    return 100.0 * (1.0 - sum(r.true_batches for r in ok) / cand)
