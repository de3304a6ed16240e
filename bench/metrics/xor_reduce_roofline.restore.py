"""xor_reduce's share of its HBM roofline in restores. Each block folded
reads its s sources and writes itself: (s + 1) * B bytes at the least."""
from bench.harness.record import roofline_pct


def read(run):
    folded = run.counters.get("blocks_folded")
    if not folded:
        return None
    blocks = run.counters["fold_sources"] + folded
    return roofline_pct(run, "xor_reduce",
                        blocks * run.config["block_size"])
