"""gf_bitmatmul's share of its HBM roofline in restores. Each stripe decoded
reads k sources and writes its lost data blocks: (k + lost) * B bytes at
the least."""
from bench.harness.record import roofline_pct


def read(run):
    stripes = run.counters.get("stripes_decoded")
    if not stripes:
        return None
    blocks = stripes * run.config["k"] + run.counters["blocks_decoded"]
    return roofline_pct(run, "gf_bitmatmul",
                        blocks * run.config["block_size"])
