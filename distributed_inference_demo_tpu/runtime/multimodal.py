"""LLaVA-style multimodal inference: vision prefix + text decode.

BASELINE.json config #5: "vision encoder on an edge client, LLM decoder
shard on TPU".  The reference has no vision path; its closest concept is
heterogeneous per-device module placement (``server.py:831-832``).  Three
pieces:

- :class:`MultimodalEngine` — single-process reference: ViT+projector
  (``models/vision.py``) encodes the image, the projected patches are
  concatenated with token embeddings, the decoder prefils the combined
  prefix and decodes with the ordinary fused scan.
- :class:`VisionWorker` — the "edge client": a transport node that
  receives images (``img:{rid}``) and answers with projected patch hidden
  states (``imgh:{rid}``).  It holds no decoder weights at all.
- :class:`MultimodalHeader` — a :class:`PipelineHeader` whose prefill
  chunk is the pre-embedded multimodal prefix (vision worker round-trip +
  local token embedding); every downstream decoder stage is unchanged —
  stages only ever see ``[b, s, H]`` hidden states, so the multimodal
  prefix needs nothing new after stage 0.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..comm import wire
from ..comm.transport import BaseTransport
from ..models.base import ModelConfig, StageParams
from ..models.decoder import embed_tokens, stage_forward
from ..models.vision import VisionConfig, vision_forward
from ..ops.sampling import SamplingParams
from .distributed import (DEFAULT_STEP_TIMEOUT, PipelineHeader, StageRuntime,
                          _Request)
from .engine import GenerationResult, InferenceEngine

log = logging.getLogger(__name__)


def make_multimodal_encode(cfg: ModelConfig, vcfg: VisionConfig):
    """Jitted (vparams, dec_params, images, text_ids) -> [b, n_img+s, H]:
    the LLaVA input recipe — projected patches prepended to the text."""

    @jax.jit
    def encode(vparams, dec_params, images, text_ids):
        img_h = vision_forward(vparams, vcfg, images).astype(cfg.dtype)
        tok = embed_tokens(dec_params, cfg, text_ids)
        return jnp.concatenate([img_h, tok], axis=1)

    return encode


class MultimodalEngine:
    """Single-process image+text generation (the parity reference for the
    distributed composition below)."""

    def __init__(self, cfg: ModelConfig, params: StageParams,
                 vcfg: VisionConfig, vparams: dict,
                 max_seq: Optional[int] = None,
                 sampling: SamplingParams = SamplingParams(),
                 eos_id: Optional[int] = None,
                 attn_backend: str = "auto",
                 kv_dtype: Optional[str] = None):
        self.engine = InferenceEngine(cfg, params, max_seq, sampling,
                                      eos_id, attn_backend,
                                      kv_dtype=kv_dtype)
        self.cfg = cfg
        self.vcfg = vcfg
        self.vparams = vparams
        self._encode = make_multimodal_encode(cfg, vcfg)
        attn_impl = self.engine._attn_impl
        spec = self.engine.spec

        @jax.jit
        def prefill_embeds(dec_params, embeds, cache):
            b, s = embeds.shape[0], embeds.shape[1]
            pos = jnp.broadcast_to(jnp.arange(s), (b, s))
            logits, cache = stage_forward(dec_params, cfg, spec, embeds,
                                          cache, pos, attn_impl=attn_impl,
                                          logits_at=s - 1)
            return logits[:, -1], cache

        self._prefill_embeds = prefill_embeds

    def generate(self, images: np.ndarray, text_ids: np.ndarray,
                 max_new_tokens: int, seed: int = 0) -> GenerationResult:
        """``images``: [b, H, W, C]; ``text_ids``: [b, s] int32."""
        eng = self.engine
        ids = jnp.asarray(text_ids, jnp.int32)
        embeds = self._encode(self.vparams, eng.params,
                              jnp.asarray(images), ids)
        b, seq = embeds.shape[0], embeds.shape[1]
        eng._check_capacity(seq, max_new_tokens)
        t0 = time.perf_counter()
        cache = eng.new_cache(b)
        logits, cache = self._prefill_embeds(eng.params, embeds, cache)
        toks, _, _ = eng._decode(eng.params, logits, cache,
                                 jax.random.PRNGKey(seed),
                                 eng._eos_scalar(), max_new_tokens)
        toks = np.asarray(toks)
        return GenerationResult(tokens=toks, prompt_len=seq,
                                num_new=max_new_tokens,
                                seconds=time.perf_counter() - t0)


class MultimodalBackend:
    """``serve --vision``: MultimodalEngine behind InferenceHTTPServer.

    POST /generate gains an optional ``image`` field — nested JSON
    floats shaped [H][W][C] (one image broadcast to the prompt batch of
    1) or [b][H][W][C] — prepended to the prompt as projected patches,
    LLaVA-style.  Requests WITHOUT an image run the wrapped text engine
    unchanged, so one server serves both modalities.  Shape and batch
    mismatches are ValueErrors (HTTP 400 with the expected tower
    geometry spelled out).  The reference has no multimodal path at all
    (BASELINE config #5 is this framework's addition)."""

    def __init__(self, engine: MultimodalEngine):
        self.mm = engine
        self._counts_lock = threading.Lock()
        self._served = {"text": 0, "image": 0}

    @property
    def max_seq(self) -> int:
        return self.mm.engine.max_seq

    def generate(self, prompt_ids: np.ndarray, max_new_tokens: int,
                 seed: int = 0, image=None,
                 logprobs: bool = False) -> GenerationResult:
        ids = np.asarray(prompt_ids, np.int32)
        if image is None:
            # text-only requests run the wrapped engine's FULL surface
            # (incl. logprobs) unchanged
            with self._counts_lock:
                self._served["text"] += 1
            return self.mm.engine.generate(ids, max_new_tokens, seed=seed,
                                           logprobs=logprobs)
        if logprobs:
            raise ValueError(
                "logprobs is not supported with image input")
        images = np.asarray(image, np.float32)
        if images.ndim == 3:
            images = images[None]
        vcfg = self.mm.vcfg
        want = (vcfg.image_size, vcfg.image_size, vcfg.channels)
        if images.ndim != 4 or images.shape[1:] != want:
            raise ValueError(
                f"image must be [H][W][C] or [b][H][W][C] with shape "
                f"{want} for this tower, got {images.shape}")
        if images.shape[0] != ids.shape[0]:
            raise ValueError(
                f"image batch {images.shape[0]} != prompt batch "
                f"{ids.shape[0]}")
        with self._counts_lock:
            self._served["image"] += 1
        return self.mm.generate(images, ids, max_new_tokens, seed=seed)

    def generate_stream(self, prompt_ids: np.ndarray, max_new_tokens: int,
                        seed: int = 0, logprobs: bool = False):
        """Text-only streaming delegates to the wrapped engine (image +
        stream is rejected at the HTTP layer — the fused multimodal
        program emits all tokens at once)."""
        with self._counts_lock:
            self._served["text"] += 1
        return self.mm.engine.generate_stream(
            np.asarray(prompt_ids, np.int32), max_new_tokens, seed=seed,
            logprobs=logprobs)

    def classify(self, prompt_ids: np.ndarray, label_token_ids):
        return self.mm.engine.classify(np.asarray(prompt_ids, np.int32),
                                       label_token_ids)

    def stats(self) -> dict:
        vcfg = self.mm.vcfg
        with self._counts_lock:
            served = dict(self._served)
        return {
            "mode": "multimodal",
            "image_size": vcfg.image_size,
            "patches_per_image": vcfg.num_patches,
            "vit_layers": vcfg.num_layers,
            "requests_text": served["text"],
            "requests_image": served["image"],
        }

    def reset_stats(self) -> None:
        with self._counts_lock:
            self._served = {"text": 0, "image": 0}


class VisionWorker:
    """The edge-client vision stage: owns ONLY the ViT+projector weights;
    serves ``img:{rid}`` -> ``imgh:{rid}`` over the transport."""

    def __init__(self, vparams: dict, vcfg: VisionConfig,
                 transport: BaseTransport, header_id: str,
                 step_timeout: float = DEFAULT_STEP_TIMEOUT):
        self.vparams = vparams
        self.transport = transport
        self.header_id = header_id
        self.step_timeout = step_timeout
        self._fwd = jax.jit(
            lambda p, img: vision_forward(p, vcfg, img))

    def serve_forever(self, idle_timeout: Optional[float] = None) -> None:
        from ..comm.transport import TransportTimeout
        while True:
            try:
                tag, payload = self.transport.recv_any(
                    timeout=idle_timeout or self.step_timeout)
            except TransportTimeout:
                log.info("vision worker %s: idle timeout, exiting",
                         self.transport.device_id)
                return
            kind, _, rest = tag.partition(":")
            if kind == "stop":
                return
            if kind != "img":
                log.warning("vision worker: unexpected tag %r", tag)
                continue
            [images] = wire.deserialize_tensors(payload).tensors
            hidden = np.asarray(self._fwd(self.vparams, jnp.asarray(images)))
            self.transport.send(self.header_id, f"imgh:{rest}",
                                wire.serialize_tensors([hidden]))


class MultimodalHeader(PipelineHeader):
    """PipelineHeader whose requests may carry an image: the prefill chunk
    becomes (vision-worker patches ++ local token embeddings), everything
    after stage 0 — ring hops, tail sampling, KV caches — is untouched."""

    def __init__(self, runtime: StageRuntime, transport: BaseTransport,
                 next_id: str, vision_id: str,
                 eos_id: Optional[int] = None,
                 step_timeout: float = DEFAULT_STEP_TIMEOUT):
        super().__init__(runtime, transport, next_id, eos_id, step_timeout)
        self.vision_id = vision_id
        self._mm_prefix: Dict[int, np.ndarray] = {}

    def _prefill_array(self, req: _Request) -> np.ndarray:
        prefix = self._mm_prefix.pop(req.rid, None)
        if prefix is None:
            return req.prompt.astype(np.int32)
        return prefix

    def _encode_image(self, images: np.ndarray) -> np.ndarray:
        """Round-trip to the vision node (the edge client)."""
        from ..comm.transport import TransportTimeout
        self.transport.send(self.vision_id, "img:0",
                            wire.serialize_tensors([np.asarray(images)]))
        deadline = time.monotonic() + self.step_timeout
        while True:
            left = deadline - time.monotonic()
            if left <= 0:
                raise TransportTimeout("vision worker did not answer")
            tag, payload = self.transport.recv_any(timeout=left)
            if tag.startswith("imgh:"):
                [hidden] = wire.deserialize_tensors(payload).tensors
                return hidden
            log.warning("header: unexpected tag %r awaiting vision", tag)

    def generate_mm(self, images: np.ndarray, text_ids: np.ndarray,
                    max_new_tokens: int, on_token=None) -> np.ndarray:
        """Image+text generation over the pipeline; returns [b, new].
        ``on_token`` streams steps exactly like ``generate_many``'s."""
        img_h = self._encode_image(images)
        tok = np.asarray(embed_tokens(self.rt.params, self.rt.cfg,
                                      jnp.asarray(text_ids, jnp.int32)))
        prefix = np.concatenate(
            [np.asarray(img_h).astype(tok.dtype), tok], axis=1)
        # capacity bookkeeping sees the combined length via a placeholder
        # id array; the real prefill input is the stashed float prefix.
        placeholder = np.zeros(prefix.shape[:2], np.int32)
        rid = self._next_rid
        self._mm_prefix[rid] = prefix
        try:
            return self.generate_many([placeholder], max_new_tokens,
                                      on_token=on_token)[0]
        finally:
            # if validation raised before _launch consumed the stash, a
            # later unrelated request would inherit this rid and prefill
            # with the wrong content — always clean up.
            self._mm_prefix.pop(rid, None)
