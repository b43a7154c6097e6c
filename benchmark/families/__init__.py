"""Block families, one module each, found by the name a configuration's
``model_config.family`` gives: ``families/<family>.py``.  Adding a family
is adding its file; no file that is here changes.

A family's module holds what is specific to one kind of decoder block and
nothing else.  The contract:

* ``equations(mc)`` returns ``(embed, layer, final_norm)``, the published
  equations as plain float32 ``jax.numpy`` (``mc`` is the configuration
  file's ``model_config`` group):

  - ``embed(params, ids)``: the rows ``[T, H]`` that enter the first
    layer, from the program's parameter tree read as data and the int32
    ids ``[T]`` (an embedding norm lives here);
  - ``layer(p, x)``: one layer over ``[T, H]``, positions ``0..T-1``; ``p``
    holds that layer's leaves by the program's names, already float32 (an
    int8 leaf is its integers times its scales); it is traced inside
    ``reference.py``'s one jitted function a layer;
  - ``final_norm(params, x)``: the norm before the head.

  They may import from ``reference.py`` the helpers every family shares
  (``F32``, ``_f32``, ``_rms_norm``, ``_layer_norm``, ``_rope``,
  ``_attention``, ``alibi_slopes``, ``_gelu_tanh``) and no line of the
  program.  ``reference.py`` keeps the layer loop, the head (tied or not by
  ``mc["tie_embeddings"]``) and its running log-sum-exp.
* ``layer_matrix_elements(mc)`` and ``layer_scale_elements(mc)``: the
  elements of one decoder layer's matrices, and their output channels (one
  float32 scale each when served as int8).  ``bytes.py`` asks the family
  for both; ``bytes.dims``, ``bytes.attention_matrix_elements`` and
  ``bytes.attention_scale_elements`` count what q, k, v and o hold.
* optionally ``kv_bytes_per_token(mc, kv_bytes, chips)``, where a token
  does not hold full keys and values for every kv head of every layer
  (``bytes.py`` has that formula as the default).

JAX and ``reference`` are imported inside ``equations`` only: the
benchmark's parent reads the shape arithmetic and never imports JAX.
"""

from __future__ import annotations

import importlib
import importlib.util
import sys


class UnknownFamily(LookupError):
    """No ``families/<family>.py``; the message names the file to add."""


def require(family: str) -> None:
    """Raise ``UnknownFamily`` unless the family's module can be found.
    Looks without importing it, so the parent can ask before it starts
    any child."""
    name = f"{__name__}.{family}"
    try:
        if name in sys.modules or importlib.util.find_spec(name) is not None:
            return
    except ImportError:             # a name with a dot in it
        pass
    raise UnknownFamily(
        f"no block family {family!r}: add benchmark/families/{family}.py "
        f"(equations, layer_matrix_elements, layer_scale_elements; "
        f"benchmark/README.md, 'Adding things')")


def load(family: str):
    """The family's module."""
    require(family)
    return importlib.import_module(f"{__name__}.{family}")
