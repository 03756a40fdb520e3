"""Batched serving engine: prefill + scanned decode with KV caches, plus the
EdgeRL *split* executor (head/tail across device/server submeshes).

``ServingEngine`` is the plain path: jit'd prefill builds the cache, a
jit'd ``lax.scan`` decodes N tokens greedily or with temperature sampling.

``SplitServingEngine`` is the paper's deployment: an EdgeRL controller
decision (version j, cut l) routes each request batch — the head segment
runs as one jit (the "UAV"/head submesh), the cut activation crosses the
link, the tail + decode runs as another jit (the edge-server submesh).
The two jits exercise exactly the partition the paper's Fig. 1 shows.
They are named ``split_head`` and ``split_tail``, so a device trace shows
them as ``jit_split_head(…)`` and ``jit_split_tail(…)``; ``infer`` is
spanned as ``split.infer`` ⊃ {``split.head``, ``split.link``,
``split.tail``} (``repro.split.*`` in a profiler trace) and counts the
wire bytes as ``split.link_bytes``.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs.base import ModelConfig
from repro.core import partition
from repro.models import model as M
from repro.obs import jaxmon


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    max_new_tokens: int = 32
    temperature: float = 0.0    # 0 => greedy
    cache_len: Optional[int] = None


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, serve: ServeConfig = ServeConfig()):
        self.cfg = cfg
        self.params = params
        self.serve = serve

        def _prefill(params, batch):
            total = serve.cache_len
            if total is None:
                total = batch["tokens"].shape[1] + serve.max_new_tokens
            return M.prefill(cfg, params, batch, total_len=total)

        def _generate(params, cache, first_tok, pos0, rng):
            def step(carry, k):
                cache, tok, pos = carry
                logits, cache = M.decode_step(cfg, params, cache, tok, pos)
                if serve.temperature > 0:
                    nxt = jax.random.categorical(
                        k, logits / serve.temperature, axis=-1)
                else:
                    nxt = jnp.argmax(logits, axis=-1)
                nxt = nxt.astype(jnp.int32)
                return (cache, nxt, pos + 1), nxt
            keys = jax.random.split(rng, serve.max_new_tokens)
            (cache, _, _), toks = jax.lax.scan(
                step, (cache, first_tok, pos0), keys)
            return toks.T, cache             # (B, N)

        self._prefill = jax.jit(_prefill)
        self._generate = jax.jit(_generate)

    def generate(self, batch: Dict, rng=None) -> jnp.ndarray:
        """batch: {tokens (B,S), [media|enc_frames]} -> (B, max_new_tokens)."""
        rng = rng if rng is not None else jax.random.key(0)
        logits, cache = self._prefill(self.params, batch)
        first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        pos0 = jnp.int32(batch["tokens"].shape[1])
        toks, _ = self._generate(self.params, cache, first, pos0, rng)
        # the prefill argmax IS generated token 0; the scan produced 1..N
        return jnp.concatenate([first[:, None], toks[:, :-1]], axis=1)


class SplitServingEngine:
    """EdgeRL-routed split inference (single forward; classification-style
    scoring of the last position, mirroring the paper's object-classifier
    workload on transformers).

    The engine holds one param tree per *quant version* (repro.quant:
    bf16 / w8 / w4), so the controller's full (version j, cut l) action is
    executable: the chosen version's quantized head runs on the device
    side, the cut activation crosses the link (int8 + scales when the
    version quantizes activations), the matching tail finishes it."""

    def __init__(self, cfg: ModelConfig, params,
                 versions: Sequence[str] = ("bf16",)):
        from repro.quant import get_version

        self.cfg = cfg
        self.params = params
        self.versions = tuple(versions)
        for v in self.versions:
            get_version(v)           # validate names up front
        self._vparams = {}           # built lazily on first infer()
        self._heads = {}
        self._tails = {}

    def _params_for(self, version: str):
        if version not in self.versions:
            raise KeyError(f"version {version!r} not enabled; have "
                           f"{sorted(self.versions)}")
        if version not in self._vparams:
            from repro.quant import build_version_params
            self._vparams[version] = build_version_params(
                self.cfg, self.params, (version,))[version]
        return self._vparams[version]

    def _fns(self, cut: Tuple[str, int], version: str):
        key = (cut, version)
        if key not in self._heads:
            cfg = self.cfg

            def split_head(p, b):
                jaxmon.count_trace("split.head")
                return partition.run_head(cfg, p, b, cut)

            def split_tail(p, a, b):
                jaxmon.count_trace("split.tail")
                return partition.run_tail(cfg, p, a, b, cut)

            self._heads[key] = jax.jit(split_head)
            self._tails[key] = jax.jit(split_tail)
        return self._heads[key], self._tails[key]

    def infer(self, batch: Dict, cut: Tuple[str, int],
              version: str = "bf16"):
        """Returns (logits, cut_activation_bytes) — the activation is what
        crosses the device->server link; its *measured* size feeds back
        into the EdgeRL env's cut_bytes axis."""
        from repro.quant import get_version, quantize_act

        with obs.span("split.infer", version=version, cut=cut,
                      S=batch["tokens"].shape[1]):
            with obs.span("split.head"):
                params = self._params_for(version)
                head, tail = self._fns(cut, version)
                act = head(params, batch)
            with obs.span("split.link"):
                if get_version(version).act_bits == 8:
                    # the link carries int8 codes + per-row scales, like
                    # the w8a8 matmuls inside the trunk
                    q, s = quantize_act(act)
                    act_bytes = (q.size * q.dtype.itemsize
                                 + s.size * s.dtype.itemsize)
                    act = (q.astype(jnp.float32) * s).astype(act.dtype)
                else:
                    act_bytes = act.size * act.dtype.itemsize
            obs.inc("split.link_bytes", act_bytes, version=version)
            with obs.span("split.tail"):
                logits = tail(params, act, batch)
        return logits, act_bytes
