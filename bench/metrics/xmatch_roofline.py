"""The cross-match kernels' share of their roofline, in percent.

The work is the bucket-scan join's (``bench/roofline.py``): per round,
each probe row against every object of its bucket.  The least time of
each round's work on the chip, summed over the window's rounds, is
divided by the device time of the cross-match programs in the trace
(every op of a jitted module whose name holds ``crossmatch``)."""

from bench.roofline import least_seconds, pair_work


def read(run):
    t = run.trace
    if not t or run.peak is None or not run.rounds:
        return None
    device_s = sum(s for name, s in t["modules"].items() if "crossmatch" in name)
    if device_s <= 0:
        return None
    shared = bool(run.cell.config["shared_plan"])
    least = 0.0
    for _, served in run.rounds:
        segs = [(rows, run.bucket_rows[b]) for b, rows in served]
        least += least_seconds(*pair_work(segs, shared), run.peak)[0]
    return 100.0 * least / device_s
