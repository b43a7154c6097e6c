"""KV manager: the bytes one request holds in the state pool whatever its
length (``/stats.kvcache.kinds.state.bytes_per_slot``), which counts the
blocks that HOLD a state: 8,536,064 here, four ``M`` blocks of ``64 x 64 x
128`` float32 and three taps of 6,144 channels, and nothing for an ``E`` or
an attention block.  ``None`` from a program without the counter."""
from layer_metrics.ssd_state_bytes_per_slot import read  # noqa: F401
