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
* optionally ``replay(mc)``, where the family does not generate one token
  a pass, left to right (``reference.emitted_logprobs`` is that account:
  one forward over prompt and emitted ids, row ``t - 1`` scores token
  ``t``; a family without a ``replay`` is scored by it and any record in
  the reply is left unread).  It returns
  ``score(params, ids, n_prompt, generation)`` which answers
  ``{"logprobs", "best_ids", "best_logprobs"}``, one entry an emitted
  token (``ids[n_prompt:]``): the plain float32 account of how this family
  produced those tokens, each scored at the pass that fixed it with the
  sequence as it stood then.

  - ``generation`` is the record the replay needs and the ids do not
    hold (at which pass each token was fixed, say).  The program writes
    it: the reply to a ``POST /generate`` with ``"logprobs": true``,
    through the gateway, carries ``"generation": [<one JSON value a
    sequence>]`` beside ``tokens`` and ``logprobs``.  ``client.ask`` keeps
    the first sequence's, ``run.reference_check`` sends it to the replica
    with ``ids`` and ``n_prompt`` and keeps it in the records file; none
    of them looks into it.  It is ``None`` where the reply had none.
  - A replay is written with ``reference.halves(params, mc)``: ``rows(ids)``,
    the rows ``[T, H]`` after the last layer (the family's own ``embed``
    and ``layer``, so a mask that is not causal lives in ``layer``), and
    ``score(x, target)``, chosen rows against chosen targets through the
    final norm and the head; and with the helpers above.  It repeats
    neither the layer loop nor the head, and imports no line of the
    program.  Call ``halves`` once: passes of one length then share the
    compiled layer function.
  - It answers ``{"error": "..."}`` where the record is missing or breaks
    the family's own rule (a token fixed at a pass whose schedule could
    not have picked it): ``run.py`` fails the run with that sentence.
  - ``tests/test_reference.py`` compares a family WITHOUT a replay with
    the program's one causal forward.  A family with one brings
    ``tests/test_<family>_family.py``, where its comparison with the
    program lives (``tests/test_families.py`` holds that the file is
    there); ``tests/test_replay.py`` shows a toy one that generates in
    blocks, with the faults a record must be shown to catch.

JAX and ``reference`` are imported inside ``equations`` and a replay's
``score`` only: the benchmark's parent reads the shape arithmetic and
never imports JAX.
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
