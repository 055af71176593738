"""Required bytes for the second family: the repository's Baichuan count
for weights and K/V, plus the per-row state that this family is taken to
keep (``row_state_size`` float32 values a row a layer, read and written
every decode step).  ``rows`` has to be told: a reader that leaves it out
would count the state of no row."""

from benchmarks.counts import baichuan as base

train_flops_per_item = base.train_flops_per_item


def row_state_bytes(cfg, layers, rows):
    return 2 * 4 * cfg["row_state_size"] * layers * rows  # read + write


def decode_step_bytes(cfg, layers, live_tokens, rows=None, bytes_per=2):
    if rows is None:
        raise TypeError("decode_step_bytes of a family with per-row state "
                        "needs rows")
    return base.decode_step_bytes(cfg, layers, live_tokens,
                                  bytes_per=bytes_per) + \
        row_state_bytes(cfg, layers, rows)
