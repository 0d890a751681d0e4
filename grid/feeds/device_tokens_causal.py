"""``input_source: device_tokens_causal``: rows of L + 1 token ids live on the
device, placed once in set-up, and every step of the scanned K-step program
draws each worker's rows from them in-graph
(``engine.build_sampled_multi_step``); the model reads a row's first L ids and
is judged on its last L (models/laguna.py), so nothing is transformed in the
step.  The program's side is ``input_source: device``'s to the letter — what
differs is the restated stream beside the plain reference
(grid/references/feed_device_tokens_causal.py), which the harness finds by
this name."""

from feeds.device import Feed  # noqa: F401
