"""InferenceEngine: continuous-batching serving loop with the DPU-analog
telemetry plane wired through it (the paper's architecture, live).

Every slot carries its own position/ring state (true continuous batching).
Where the model's cache is the per-layer K/V cache of ``decoder_fwd``, the
slot cache is layer-major and the decode step writes one position per slot
in place (``Model.decode_step_inplace``, the cache donated); any other cache
is a stacked pytree that the Model's single-sequence step, vmapped over
slots, decodes.  Telemetry taps emit the exact event schema the detectors
consume: INGRESS on request arrival, H2D around prefill feeds, DISPATCH per
step, D2H per step, EGRESS per token, QUEUE_SAMPLE per scheduler tick — and
the engine implements EngineControls so the mitigation controller can close
the loop (§5).

Each phase of the loop is a host span (``SPANS``) in the profiler's own
trace, so a traced run puts the device's idle time down to engine phases on
the device trace's clock.  A span costs about a microsecond when no
profiler runs, so they are always on; the engine itself reads no clock.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation as _span

from repro.core.detectors import (
    META_DIR_INGRESS,
    META_FIN,
    META_KV_OCC,
)
from repro.core.events import EventBatchBuilder, EventKind
from repro.core.mitigation import MitigationController
from repro.core.telemetry import TelemetryPlane
from repro.models import Model
from repro.serving.kvcache import PagedKVPool
from repro.serving.scheduler import Scheduler, SchedulerConfig, ServeRequest

# The engine's host spans.  ``engine.<phase>.<part>`` lies inside
# ``engine.<phase>``, and ``engine.prefill`` inside ``engine.admit``.
# ``engine.prefill`` carries req_id, slot and bucket, ``engine.decode`` step
# and slots (the running count), ``engine.flush`` events (the batch's
# length).
SPANS = (
    "engine.admit",                 # _admit_loop, enclosing the prefills
    "engine.prefill",               # _prefill
    "engine.prefill.dispatch",      # token array, device_put, prefill_one
    "engine.prefill.insert",        # the slot cache's .at[slot].set
    "engine.prefill.readback",      # the first token's argmax to the host
    "engine.decode",                # _step
    "engine.decode.dispatch",       # token array, device_put, decode step
    "engine.decode.readback",       # the tokens' argmax to the host
    "engine.decode.bookkeep",       # per-slot loop, egress, KV occupancy
    "engine.flush",                 # _flush_telemetry
    "engine.flush.observe",         # the plane's or sidecar's observe_batch
    "engine.flush.advance",         # the sidecar's advance
)


@dataclass
class EngineConfig:
    max_slots: int = 8
    max_seq: int = 256
    page_size: int = 16
    n_pages: int = 512
    node: int = 0
    telemetry: bool = True
    mitigate: bool = True
    greedy: bool = True
    # "instant" — in-process MitigationController (legacy topology);
    # "dpu"     — telemetry crosses a modeled transport into a DPUSidecar
    #             and mitigation commands ride the command bus back
    control: str = "instant"
    dpu: "object | None" = None      # repro.dpu.DPUParams override
    dpu_seed: int = 0                # sidecar wire RNG (XORed with node)


_LAYER_KV = frozenset(("k", "v", "kpos", "pos"))


def decodes_in_place(model: Model) -> bool:
    """Whether the model's cache is ``decoder_fwd``'s per-layer K/V cache
    and nothing else (dense, moe, vlm): such a model decodes in place."""
    return set(jax.eval_shape(lambda: model.init_cache(1, 1))) == _LAYER_KV


def _slot_kv(k: jax.Array) -> jax.Array:
    """A prefill's K or V, (L, 1, S, Hkv, hd), in the slot cache's
    layer-major layout (L, 1, Hkv, S, hd)."""
    return jnp.swapaxes(k, 2, 3)


def slot_cache(model: Model, slots: int, max_seq: int) -> dict:
    """The engine's cache for ``slots`` sequences.  For a model that
    decodes in place: K/V (L, slots, Hkv, S, hd), kpos (slots, S), pos
    (slots,); else every leaf of ``init_cache(1, max_seq)`` with a leading
    slot axis."""
    one = model.init_cache(1, max_seq)
    if decodes_in_place(model):
        return {"k": jnp.repeat(_slot_kv(one["k"]), slots, axis=1),
                "v": jnp.repeat(_slot_kv(one["v"]), slots, axis=1),
                "kpos": jnp.repeat(one["kpos"][None], slots, axis=0),
                "pos": jnp.repeat(one["pos"][None], slots, axis=0)}
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a, (slots,) + a.shape).copy(), one)


def insert(full: dict, one: dict, slot: int) -> dict:
    """The slot cache with ``slot`` replaced by a prefill's cache ``one``
    (``init_cache(1, max_seq)`` as the prefill left it)."""
    if set(full) == _LAYER_KV:
        return {"k": full["k"].at[:, slot:slot + 1].set(_slot_kv(one["k"])),
                "v": full["v"].at[:, slot:slot + 1].set(_slot_kv(one["v"])),
                "kpos": full["kpos"].at[slot].set(one["kpos"]),
                "pos": full["pos"].at[slot].set(one["pos"])}
    return jax.tree.map(lambda f, o: f.at[slot].set(o[...]), full, one)


def decode_fn(model: Model):
    """The engine's jitted decode step: (params, tokens (slots, 1, 1),
    slot cache) -> (logits (slots, 1, 1, V), slot cache).  In place, with
    the cache donated, where the model decodes in place; else the
    single-sequence step vmapped over slots.  The params are an argument,
    not a closed-over constant, so the weights stay out of the compiled
    program and its cache key."""
    if decodes_in_place(model):
        return jax.jit(model.decode_step_inplace, donate_argnums=(2,))
    return jax.jit(jax.vmap(model.decode_step, in_axes=(None, 0, 0)))


class InferenceEngine:
    """Single-host serving engine (smoke scale on CPU, shardable on TPU)."""

    def __init__(self, model: Model, params, cfg: EngineConfig | None = None,
                 plane: TelemetryPlane | None = None) -> None:
        self.model = model
        self.params = params
        self.cfg = cfg or EngineConfig()
        self.sched = Scheduler(SchedulerConfig(max_slots=self.cfg.max_slots))
        self.pool = PagedKVPool(self.cfg.n_pages, self.cfg.page_size)
        self.plane = plane
        if self.plane is None and self.cfg.telemetry:
            self.plane = TelemetryPlane(n_nodes=1, mitigate=self.cfg.mitigate)
        # telemetry sink: the plane directly (instant) or a DPU sidecar
        # whose command bus actuates this engine (dpu)
        if self.cfg.control not in ("instant", "dpu"):
            raise ValueError(
                f"unknown EngineConfig.control {self.cfg.control!r} "
                "(expected 'instant' or 'dpu')")
        self.dpu = None
        self._sink = self.plane
        if self.plane is not None and self.cfg.control == "dpu":
            from repro.dpu import DPUSidecar
            # per-replica wire seed: correlated loss across a ReplicaSet's
            # engines would be an accidental common-mode failure
            self.dpu = DPUSidecar(self.plane, self.cfg.dpu, engine=self,
                                  seed=self.cfg.dpu_seed ^ self.cfg.node,
                                  mitigate=self.cfg.mitigate)
            self._sink = self.dpu
        elif self.plane is not None and self.plane.controller is not None:
            self.plane.controller.engine = self
        # the engine lives on the device that holds its params: caches and
        # step inputs are placed beside them, so replicas whose params sit
        # on different chips each run on their own chip
        self.device = jax.tree.leaves(params)[0].device
        # stacked per-slot caches: leaf shape (slots, ...)
        with jax.default_device(self.device):
            self.slot_cache = slot_cache(model, self.cfg.max_slots,
                                         self.cfg.max_seq)
        self._decode = decode_fn(model)
        self._inplace = decodes_in_place(model)
        self._prefill_jit: dict[int, callable] = {}
        # observer of every logits row the engine computes:
        # on_logits({row: request}, consumed tokens (rows, n), logits
        # (rows, vocab)) — how a caller checks served logits against a
        # reference without a second code path
        self.on_logits = None
        self.clock = 0.0
        self.completed: list[ServeRequest] = []
        self.kv_compress = False
        # telemetry back-pressure knob: emit low-priority samples (KV
        # occupancy) every Nth step; throttle_telemetry doubles the stride
        self.telemetry_stride = 1
        # prefill_positions counts the bucket positions the prefill
        # programs computed, prefill_tokens the prompt tokens among them;
        # inplace_steps the decode steps that wrote the cache in place
        self.stats = {"steps": 0, "tokens": 0, "prefills": 0,
                      "prefill_tokens": 0, "prefill_positions": 0,
                      "inplace_steps": 0}
        # telemetry taps accumulate columnar rows; one batch per step goes
        # to the plane (the engine feeds the same line-rate path as the sim)
        self._pending = EventBatchBuilder()

    # ------------------------------------------------------------------
    # EngineControls (mitigation actuation surface)
    # ------------------------------------------------------------------

    def apply_action(self, action: str, node: int, detail: dict) -> bool:
        if action == "inflight_remap":
            self.sched.set_continuous(True)
            return True
        if action == "widen_batch_window":
            self.sched.set_batch_window(
                max(self.sched.cfg.batch_window * 2, 2e-3))
            return True
        if action == "admission_control":
            self.sched.pause_admission(self.clock + 0.05)
            return True
        if action == "smooth_admission":
            self.sched.set_batch_window(
                max(self.sched.cfg.batch_window, 1e-3))
            return True
        if action == "compress_kv":
            self.kv_compress = True
            return True
        if action == "throttle_telemetry":
            self.telemetry_stride = min(self.telemetry_stride * 2, 64)
            return True
        if action in ("rebalance_microbatches", "rebalance_shards",
                      "rebalance_frontend", "pin_and_coalesce",
                      "batch_launches"):
            return True     # accepted; no-op at single-host smoke scale
        return False

    # ------------------------------------------------------------------
    # request path
    # ------------------------------------------------------------------

    def submit(self, req: ServeRequest) -> None:
        self.sched.submit(req)
        self._emit(EventKind.INGRESS_PKT, flow=req.req_id,
                   size=2 * req.prompt_len, meta=META_DIR_INGRESS)

    def _emit(self, kind: EventKind, **kw) -> None:
        if self.plane is not None:
            self._pending.add(ts=self.clock, kind=kind,
                              node=self.cfg.node, **kw)

    def _flush_telemetry(self) -> None:
        with _span("engine.flush", events=len(self._pending)):
            if self.plane is None:
                return
            if len(self._pending):
                batch = self._pending.build(sort=True)
                self._pending.clear()
                with _span("engine.flush.observe"):
                    self._sink.observe_batch(batch)
            if self.dpu is not None:
                with _span("engine.flush.advance"):
                    self.dpu.advance(self.clock)

    def _prefill_fn(self, bucket: int):
        if bucket not in self._prefill_jit:
            model, max_seq = self.model, self.cfg.max_seq

            def prefill_one(params, tokens):
                return model.prefill(params, tokens,
                                     model.init_cache(1, max_seq))

            self._prefill_jit[bucket] = jax.jit(prefill_one)
        return self._prefill_jit[bucket]

    def _admit_loop(self) -> None:
        with _span("engine.admit"):
            while True:
                if not self.sched.queue:
                    break
                head = self.sched.queue[0]
                need = head.prompt_len + head.max_new_tokens
                if not self.pool.can_admit(need):
                    # paper §5: early KV eviction under pressure
                    if self.pool.evict_lru() is None:
                        break
                    continue
                got = self.sched.admit(self.clock)
                if got is None:
                    break
                slot, req = got
                self.pool.allocate(req.req_id, need)
                self._prefill(slot, req)

    def _prefill(self, slot: int, req: ServeRequest) -> None:
        bucket = self.sched.bucket_len(req.prompt_len)
        with _span("engine.prefill", req_id=req.req_id, slot=slot,
                   bucket=bucket):
            with _span("engine.prefill.dispatch"):
                toks = np.zeros((1, bucket), np.int32)
                toks[0, -req.prompt_len:] = req.prompt   # left-pad
                self._emit(EventKind.H2D_XFER, device=slot % 4,
                           size=int(toks.size * 4), flow=req.req_id)
                self._emit(EventKind.DISPATCH, device=slot % 4)
                logits, cache = self._prefill_fn(bucket)(
                    self.params, jax.device_put(toks, self.device))
            if self.on_logits is not None:
                self.on_logits({0: req}, toks, logits[:, -1])
            # first-token logits return to the host (pairs with the
            # dispatch)
            self._emit(EventKind.D2H_XFER, device=slot % 4,
                       size=int(logits.size * 4), flow=req.req_id)
            # write the per-slot cache
            with _span("engine.prefill.insert"):
                self.slot_cache = insert(self.slot_cache, cache, slot)
            with _span("engine.prefill.readback"):
                nxt = int(jnp.argmax(logits[0, -1]))
            req.tokens_out = 0
            req.first_token = -1.0
            self._slot_next_token[slot] = nxt
            self.stats["prefills"] += 1
            self.stats["prefill_tokens"] += req.prompt_len
            self.stats["prefill_positions"] += bucket

    # ------------------------------------------------------------------
    # decode loop
    # ------------------------------------------------------------------

    _slot_next_token: dict

    def run(self, requests: list[ServeRequest], max_steps: int = 2000,
            step_time: float = 2e-3) -> dict:
        """Drive the engine until all requests finish (or step budget)."""
        self._slot_next_token = {}
        pending = sorted(requests, key=lambda r: r.arrival)
        i = 0
        for step in range(max_steps):
            self.clock += step_time
            while i < len(pending) and pending[i].arrival <= self.clock:
                self.submit(pending[i])
                i += 1
            self._emit(EventKind.QUEUE_SAMPLE,
                       depth=self.sched.queue_depth(),
                       meta=META_DIR_INGRESS)
            self._admit_loop()
            if self.sched.running:
                self._step()
            self._flush_telemetry()
            if i >= len(pending) and not self.sched.running \
                    and not self.sched.queue:
                break
        return self.report()

    def _step(self) -> None:
        slots = sorted(self.sched.running)
        with _span("engine.decode", step=self.stats["steps"],
                   slots=len(slots)):
            with _span("engine.decode.dispatch"):
                toks = np.zeros((self.cfg.max_slots, 1, 1), np.int32)
                for s in slots:
                    toks[s, 0, 0] = self._slot_next_token.get(s, 0)
                self._emit(EventKind.DISPATCH, device=0)
                # a donated cache is gone once passed: rebind at once
                logits, self.slot_cache = self._decode(
                    self.params, jax.device_put(toks, self.device),
                    self.slot_cache)
            if self.on_logits is not None:
                self.on_logits({s: self.sched.running[s] for s in slots},
                               toks[:, 0], logits[:, 0, -1])
            self._emit(EventKind.D2H_XFER, device=0,
                       size=len(slots) * 4)
            self.stats["steps"] += 1
            self.stats["inplace_steps"] += self._inplace
            with _span("engine.decode.readback"):
                nxt = np.asarray(jnp.argmax(logits[:, 0, -1], axis=-1))
            with _span("engine.decode.bookkeep"):
                self._bookkeep(slots, nxt)

    def _bookkeep(self, slots: list[int], nxt: np.ndarray) -> None:
        """After a decode step: advance each running slot, release the
        finished ones, and queue the step's telemetry."""
        eg_flow: list[int] = []
        eg_meta: list[int] = []
        for s in slots:
            req = self.sched.running[s]
            if req.first_token < 0:
                req.first_token = self.clock
            req.tokens_out += 1
            self.stats["tokens"] += 1
            self.pool.extend(req.req_id)
            self._slot_next_token[s] = int(nxt[s])
            fin = req.tokens_out >= req.max_new_tokens
            eg_flow.append(req.req_id)
            eg_meta.append(META_FIN if fin else 0)
            if fin:
                self.sched.release(s, self.clock)
                self.pool.free(req.req_id)
                self.completed.append(req)
        # token egress leaves as one columnar append per step (the same
        # bulk path the simulator's producer plane uses)
        if self.plane is not None and eg_flow:
            self._pending.add_columns(
                np.full(len(eg_flow), self.clock), EventKind.EGRESS_PKT,
                node=self.cfg.node,
                flow=np.asarray(eg_flow, np.int64),
                size=8 if not self.kv_compress else 4,
                group=self.cfg.node,
                meta=np.asarray(eg_meta, np.int64))
        # KV occupancy sample (Table 2b) — the low-priority event class the
        # throttle_telemetry actuation strides down
        if self.stats["steps"] % self.telemetry_stride == 0:
            self._emit(EventKind.QUEUE_SAMPLE,
                       depth=int(self.pool.occupancy() * 100),
                       meta=META_KV_OCC)

    # ------------------------------------------------------------------

    def report(self) -> dict:
        self._flush_telemetry()
        lats = sorted(r.latency for r in self.completed)
        ttfts = sorted(r.ttft for r in self.completed)

        def pct(xs, q):
            return xs[min(int(q * len(xs)), len(xs) - 1)] if xs else None
        rep = {
            "completed": len(self.completed),
            "steps": self.stats["steps"],
            "tokens": self.stats["tokens"],
            "tokens_per_step": self.stats["tokens"]
            / max(self.stats["steps"], 1),
            "p50_latency": pct(lats, 0.5),
            "p99_latency": pct(lats, 0.99),
            "p50_ttft": pct(ttfts, 0.5),
            "kv_occupancy": self.pool.occupancy(),
            "evictions": self.pool.stats.evictions,
        }
        if self.plane is not None:
            rep["telemetry"] = self.plane.report()
        return rep
