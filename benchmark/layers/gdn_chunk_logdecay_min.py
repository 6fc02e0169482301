"""The least sum of the linear layers' decay exponent `g` over a chunk of 64
positions in the update's causal pass, over layers, heads and rows, and over
the window's rows (`gdn_chunk_logdecay_min` of `models/seq_policy.py::trunk`,
through `Unrolled.metrics` and `train.py`'s log rows). It says whether a
factored `exp(G_i) exp(-G_j)`, the usual next speed-up of the chunked scan,
would stay inside float32 (whose `exp` overflows past 88.7). A program
without the counter reads nothing.

A file and NOT a manifest entry (see `attn_linear_ms.py`)."""
LAYER, UNIT, SOURCE = "sequence policy", "ln", "program_counter"
MOVES = "fused_steps_per_s"


def read(run, ctx):
    values = [row["gdn_chunk_logdecay_min"] for row in run.get("rows") or []
              if "gdn_chunk_logdecay_min" in row]
    return min(values) if values else None
