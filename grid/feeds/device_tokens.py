"""``input_source: device_tokens``: the token rows live on the device, placed
once in set-up, and every step of the scanned K-step program draws each
worker's sequences from them in-graph and noises them in the step
(``engine.build_sampled_multi_step`` and the experiment's
``device_transform``).  The program's side is ``input_source: device``'s to the
letter — what differs is the restated stream beside the plain reference
(grid/references/feed_device_tokens.py), which the harness finds by this name."""

from feeds.device import Feed  # noqa: F401
