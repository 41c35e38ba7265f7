"""Kernels: the ``bitmap_extract`` compaction's share (%) of its HBM
roofline.

Minimum bytes per query with candidates: its W = ceil(n_batches / 32)
bitmap words read and one 4-byte id written per candidate batch.  Rows
without candidates, padding rows and padding slots are left out.  Time:
the device time of the kernel's ops in the traced window.
"""
from bench import peaks, trace

#: The kernel's op in a TPU trace: its custom call takes the name of the
#: jitted wrapper around ``pallas_call`` (the kernel body's name kept as a
#: second spelling).
KERNEL = ("bitmap_extract_pallas", "_extract_kernel")


def read(run):
    if run.trace is None:
        return None
    secs = trace.op_seconds(run.trace,
                            lambda name: any(k in name for k in KERNEL))
    if secs <= 0:
        if run.stats["device_waves"] and any(r.candidates
                                             for r in run.answered()):
            raise RuntimeError(f"no op named like {KERNEL} in the traced "
                               f"window, though device waves had "
                               f"candidates to extract: the kernel's op "
                               f"name has changed")
        return None
    w = run.words
    nbytes = sum(4 * (w + r.candidates) for r in run.answered()
                 if r.candidates)
    bw = peaks.peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * nbytes / bw / secs
