"""Batched serving engine: prefill + decode with continuous batching.

A fixed pool of ``batch_slots`` decode lanes runs one jit'd decode step per
tick over the whole pool (caches are [L, B, ...] arrays — the exact shapes
the ``decode_*`` dry-run cells lower).  New requests are prefilled
individually (a second jit'd program) and their caches inserted into a free
lane; finished lanes (EOS or ``max_new``) are evicted and refilled —
vLLM-style continuous batching reduced to its JAX-native core.

Greedy and temperature sampling; per-request token logs; deterministic
given the seed.  Lane occupancy lives in the shared
:class:`repro.serve.lanes.LanePool` — the same insert/step/evict shape
the streaming dispatch engine (:mod:`repro.stream`) reuses for
scheduling instead of decoding.

Semantics contracts (regression-locked in ``tests/test_serve.py``):

* ``max_new`` counts **decode** tokens; the prefill-sampled continuation
  token is emitted in addition (``out_tokens`` holds ``1 + max_new`` ids
  for an un-truncated, non-EOS request);
* a request evicted at the ``max_len`` KV horizon before reaching
  ``max_new``/EOS is surfaced with ``truncated=True``, never silently;
* ``run`` drains the lane pool before returning — unfinished requests come
  back ``done=False`` *and* their lanes are freed, so back-to-back ``run``
  calls on one engine never re-serve stale lanes.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.api import Model
from repro.models.common import ArchConfig
from repro.models.parallel import ParallelCfg
from repro.obs import MetricsRegistry, Tracer, get_tracer
from repro.serve.lanes import LanePool


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # [S] int32
    max_new: int = 16
    out_tokens: list[int] = dataclasses.field(default_factory=list)
    done: bool = False
    truncated: bool = False            # evicted at the max_len KV horizon


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    batch_slots: int = 4
    max_len: int = 256                 # KV-cache horizon per lane
    temperature: float = 0.0           # 0 = greedy
    eos_id: int = -1                   # -1: never EOS (synthetic vocab)
    seed: int = 0


class ServeEngine:
    def __init__(self, model: Model, params, cfg: ArchConfig,
                 par: ParallelCfg, sc: ServeConfig = ServeConfig(),
                 tracer: Tracer | None = None,
                 metrics: MetricsRegistry | None = None):
        self.model, self.params, self.cfg, self.par, self.sc = \
            model, params, cfg, par, sc
        # Host-side telemetry (repro.obs): never inside jitted code, so
        # sampled tokens are bit-exact with tracing on or off.  The tick
        # index is the simulation clock for trace timestamps.
        self.tracer = tracer if tracer is not None else get_tracer()
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self._wall_seen: set[str] = set()
        self._tick = 0
        self._decode = jax.jit(
            lambda p, b: model.decode(p, b, cfg, par))
        self._prefill = jax.jit(
            lambda p, b: model.prefill(p, b, cfg, par))
        self._key = jax.random.key(sc.seed)
        self.caches: dict[str, Any] | None = None
        self.lanes = LanePool(sc.batch_slots)
        self.lane_pos = np.zeros(sc.batch_slots, np.int32)

    # -- cache pool -----------------------------------------------------------
    def _init_caches(self, template: dict) -> None:
        """Allocate the lane pool from a single-request prefill's caches.

        KV time dims are resized to the ``max_len`` horizon; SSM/conv/cross
        caches keep their shapes."""
        B, M = self.sc.batch_slots, self.sc.max_len
        pool = {}
        for k, v in template.items():
            shape = (v.shape[0], B) + v.shape[2:]
            if k in ("k_cache", "v_cache"):
                W = min(v.shape[2], M) if self.cfg.attn_window else M
                shape = (v.shape[0], B, W) + v.shape[3:]
            pool[k] = jnp.zeros(shape, v.dtype)
        self.caches = pool

    def _insert(self, lane: int, caches_1: dict, prompt_len: int) -> None:
        for k, v in caches_1.items():
            pool = self.caches[k]
            if k in ("k_cache", "v_cache"):
                W = pool.shape[2]
                if v.shape[2] >= W:
                    src = v[:, :, :W]
                else:
                    src = jnp.pad(v, [(0, 0), (0, 0), (0, W - v.shape[2])]
                                  + [(0, 0)] * (v.ndim - 3))
            else:
                src = v
            self.caches[k] = pool.at[:, lane].set(src[:, 0])

    # -- telemetry ------------------------------------------------------------
    def _observe_wall(self, name: str, seconds: float) -> None:
        """first = jit compile + execute span (or a warm process-cache hit);
        the rest are warm steps — the compile/warm split summary() reports."""
        suffix = "_first" if name not in self._wall_seen else "_warm"
        self._wall_seen.add(name)
        self.metrics.histogram(name + suffix).observe(seconds)

    def summary(self) -> dict:
        """Aggregate view of the last ``run`` from the metrics registry."""
        snap = self.metrics.snapshot()
        return {
            "requests_admitted": snap.get("requests_admitted", 0),
            "requests_completed": snap.get("requests_completed", 0),
            "requests_truncated": snap.get("requests_truncated", 0),
            "decode_tokens": snap.get("decode_tokens", 0),
            "ticks": snap.get("ticks", 0),
            "wall": {k: v for k, v in snap.items()
                     if k.startswith(("decode_wall_s", "prefill_wall_s"))},
        }

    # -- scheduling -----------------------------------------------------------
    def _admit(self, queue: list[Request]) -> None:
        for lane, req in self.lanes.admit(queue):
            t0 = time.perf_counter()
            batch = {"tokens": jnp.asarray(req.prompt[None, :])}
            if self.cfg.n_encoder_layers:
                batch["frame_embeds"] = jnp.zeros(
                    (1, len(req.prompt), self.cfg.d_model), jnp.bfloat16)
            if self.cfg.frontend == "vision_stub":
                P = min(self.cfg.n_frontend_tokens, 8)
                batch["patch_embeds"] = jnp.zeros(
                    (1, P, self.cfg.d_model), jnp.bfloat16)
            logits, caches_1 = self._prefill(self.params, batch)
            jax.block_until_ready(caches_1)   # prefill_wall_s covers the solve
            if self.caches is None:
                self._init_caches(caches_1)
            self._insert(lane, caches_1, len(req.prompt))
            tok = self._sample(logits)[0]
            req.out_tokens.append(int(tok))
            self.lane_pos[lane] = len(req.prompt)
            self._observe_wall("prefill_wall_s", time.perf_counter() - t0)
            self.metrics.counter("requests_admitted").inc()
            self.tracer.instant("admit", self._tick, rid=req.rid, lane=lane,
                                prompt_len=len(req.prompt))

    def _sample(self, logits: jnp.ndarray) -> np.ndarray:
        logits = logits[..., :self.cfg.vocab_size]
        if self.sc.temperature <= 0:
            return np.asarray(jnp.argmax(logits, -1))
        self._key, k = jax.random.split(self._key)
        return np.asarray(jax.random.categorical(
            k, logits / self.sc.temperature))

    # -- main loop ------------------------------------------------------------
    def run(self, requests: list[Request], max_ticks: int = 10_000
            ) -> list[Request]:
        queue = list(requests)
        done: list[Request] = []
        self.metrics.reset()
        self._wall_seen = set()
        self._tick = 0
        for _ in range(max_ticks):
            self._admit(queue)
            active = [l for l, _ in self.lanes.active()]
            if not active:
                if not queue:
                    break
                continue
            if self.tracer.enabled:
                self.tracer.counter("lanes_active", self._tick, len(active))
            t0 = time.perf_counter()
            # Pool decode tick: every lane advances one token at its own
            # position (decode_step supports per-lane pos vectors).
            last = jnp.asarray(
                [r.out_tokens[-1] if r else 0 for r in self.lanes.payloads()],
                jnp.int32)[:, None]
            batch = {"token": last, "pos": jnp.asarray(self.lane_pos),
                     **self.caches}
            logits, self.caches = self._decode(self.params, batch)
            toks = self._sample(logits)          # host sync (np.asarray)
            self._observe_wall("decode_wall_s", time.perf_counter() - t0)
            self.metrics.counter("ticks").inc()
            self.metrics.counter("decode_tokens").inc(len(active))
            for lane in active:
                req = self.lanes.payload(lane)
                req.out_tokens.append(int(toks[lane]))
                self.lane_pos[lane] += 1
                # max_new counts *decode* tokens — the prefill-sampled token
                # (out_tokens[0]) is in addition, not one of the max_new.
                n_decode = len(req.out_tokens) - 1
                finished = (toks[lane] == self.sc.eos_id
                            or n_decode >= req.max_new)
                horizon = self.lane_pos[lane] >= self.sc.max_len - 1
                if finished or horizon:
                    req.done = True
                    req.truncated = bool(horizon and not finished)
                    done.append(req)
                    self.lanes.evict(lane)
                    self.metrics.counter("requests_completed").inc()
                    if req.truncated:
                        self.metrics.counter("requests_truncated").inc()
                    self.tracer.instant("evict", self._tick, rid=req.rid,
                                        lane=lane, tokens=len(req.out_tokens),
                                        truncated=req.truncated)
            self._tick += 1
        # Drain: whatever is still in flight comes back done=False, but its
        # lane is freed — a second run() on this engine starts clean instead
        # of double-serving stale lanes.
        leftover = self.lanes.drain()
        self.lane_pos[:] = 0
        return done + leftover
