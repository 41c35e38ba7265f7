"""Kernels: the ``bitset_ops`` fold's share (%) of its HBM roofline.

Minimum bytes per query, from the logical shape of its wave: its T real
token planes of W = ceil(n_batches / 32) words read, one combined plane
written, one count written.  Padding of Q, T and W is left out, so a
change that removes padding raises the share.  Time: the device time of
the kernel's ops in the traced window.
"""
from bench import peaks, trace

#: The kernel's op in a TPU trace: its custom call takes the name of the
#: jitted wrapper around ``pallas_call`` (the kernel body's name kept as a
#: second spelling).
KERNEL = ("bitset_reduce_batch_pallas", "_bitset_batch_kernel")


def read(run):
    if run.trace is None:
        return None
    secs = trace.op_seconds(run.trace,
                            lambda name: any(k in name for k in KERNEL))
    if secs <= 0:
        if run.stats["device_waves"]:
            raise RuntimeError(f"no op named like {KERNEL} in the traced "
                               f"window, though device waves ran: the "
                               f"kernel's op name has changed")
        return None
    w = run.words
    nbytes = sum(4 * (run.n_tokens[r.query] * w + w + 1)
                 for r in run.answered() if run.n_tokens[r.query])
    bw = peaks.peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * nbytes / bw / secs
