"""gf_bitmatmul's share of its HBM roofline in checksummed restores, where
it computes the CRC32C of every block read and no decode runs. Each block
a restore must check, at the least, is read once and its CRCs written,
4 bytes per `bytes_per_checksum`: B * (1 + 4 / bytes_per_checksum)."""
from bench.harness.record import roofline_pct


def read(run):
    blocks = run.counters.get("blocks_to_verify")
    if not blocks:
        return None
    B = run.config["block_size"]
    return roofline_pct(run, "gf_bitmatmul", blocks * B * (
        1 + 4 / run.config["bytes_per_checksum"]))
