"""gf_bitmatmul's share of its HBM roofline in saves. Each stripe encoded
reads its k data blocks and writes its n - k parities: n * B bytes at the
least."""
from bench.harness.record import roofline_pct


def read(run):
    stripes = run.counters.get("stripes_encoded")
    if not stripes:
        return None
    floor = stripes * run.config["n"] * run.config["block_size"]
    return roofline_pct(run, "gf_bitmatmul", floor)
