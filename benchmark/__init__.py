"""The benchmark: the yardstick this repository's speed claims are held to.

Everything that decides a number lives here, where a PR that claims a gain
cannot change it: traffic generation, the reduction from traces and spans to
metrics, the table of peaks, the operation counts, the plain references and
the comparison that decides `correct`. From the program it takes only the
system under test (through `train.main` and `scripts/serve.py`'s `main`), its
spans, counters and module names. `BENCHMARK.json` at the root is the manifest;
`run.py` is the one command.
"""
