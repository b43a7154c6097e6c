"""Block-level KV cache with radix-tree prefix sharing (docs/DESIGN.md
§10 the tree, §11 the page pool, §14 the backend seam).

The single prefix-reuse path for the serving stack, behind the
:mod:`~.backend` seam every engine consumes.  A KV cache is one pool
of pages on the device: the batching scheduler's slot cache IS a page
pool addressed through block tables, the ring stage workers hold
per-stage page pools, and the single-request engines keep a
device-resident prefix pool (:class:`~.backend.PagedKVBackend`).  One
manager of page ids (:class:`~.paged.PagedKVCacheManager`) and one
radix tree over them (:class:`~.radix.RadixTree`) serve all three.
Hits are device gathers / block-table references, stores are device
scatters / ownership adoptions — zero bytes cross the host boundary
(``dwt_kvcache_h2d_bytes_total`` counts only §21 tier promotions).

Page WIDTH selection (docs/DESIGN.md §17): the ``kv_dtype`` kwarg /
``--kv-dtype`` flag over ``DWT_KV_DTYPE`` over ``bf16``, funneled
through :func:`resolve_kv_dtype` (owned by ``ops/quant.py`` next to the
quantized-page rails, re-exported here) — called at every pool-creation
site, so the env knob reaches engines without an explicit kwarg.
"""

from ...ops.quant import KV_DTYPES, resolve_kv_dtype
from .backend import PagedKVBackend, make_kv_backend
from .paged import (DEFAULT_BLOCK_TOKENS, PagedBlockLease,
                    PagedKVCacheManager, resolve_kvcache_config)
from .radix import RadixTree
from .tiered import (TieredKVStore, make_demote_hook, promote_prefix,
                     resolve_tier_config)

__all__ = ["PagedKVBackend", "make_kv_backend",
           "PagedBlockLease", "PagedKVCacheManager", "RadixTree",
           "resolve_kvcache_config",
           "resolve_kv_dtype", "DEFAULT_BLOCK_TOKENS",
           "KV_DTYPES",
           "TieredKVStore", "make_demote_hook", "promote_prefix",
           "resolve_tier_config"]
