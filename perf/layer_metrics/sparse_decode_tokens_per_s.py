"""Step programs: tokens a second the window's decode steps emitted —
decode queries of the layers with an indexer (the program's
`sparse.decode_queries`) / those layers / the window. It is
`serve_out_tokens_per_s` less each request's first token (a prefill's),
read from the program's own counter: the cell is not held to tokens a
second (PERF.md 6, PR 30: 40 s windows of it spread 10-15 % at this
traffic), so the ledger keeps it here, unbounded."""
from harness import manifest

_rows = manifest.load_plugin("layer_metrics",
                             "sparse_decode_rows_per_step_mean")


def read(rec):
    rows = _rows.rows_decoded(rec)
    if not rows:
        return None
    t_ws, t_we = rec["window"]
    return rows / (t_we - t_ws)
