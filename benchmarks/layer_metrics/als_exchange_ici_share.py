"""The exchange's share of the inter-chip peak: the least bytes a chip must
send in an iteration's exchanges (``counts_sharded.exchange_bytes_per_chip``)
at the chip's published inter-chip bandwidth, over ``als_exchange_ms``."""

from benchmarks import counts_sharded, scopes_sharded


def read(run):
    ms, sent = scopes_sharded.exchange_ms(run), run.get("exchange_bytes_per_iteration")
    if not ms or not sent:
        return None
    return counts_sharded.ici_share_pct(sent, ms / 1000.0, run["device_kind"])
