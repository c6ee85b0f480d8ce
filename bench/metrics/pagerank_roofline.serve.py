"""The segment PageRank program: share of the HBM roofline.  The bytes its
iterations must move (`bench/fixpoint_costs.py`, at each solve's padded
edge length times its iterations, over the PageRank documents answered in
the window) over its programs' device time, over the device's peak bytes
per second."""
from bench.peaks import peaks
from bench.readers import ratio
from bench.serve_readers import program_seconds


def read(run):
    secs = program_seconds(run, "pagerank_fixpoint_kernel")
    share = ratio(run.window.get("pagerank_bytes"), secs, 100.0)
    return None if share is None else (
        share / peaks(run.device_kind)["hbm_bytes_per_s"])
