"""Batched decode engine with slot-based continuous batching.

Requests occupy fixed batch slots; finished slots are refilled from the
queue each step (decode-time continuous batching). The KV/recurrent state
is allocated once at ``max_len`` and reused across requests per slot.

Slot refill uses a *batched prefill*: the prompts of every newly seated
request are pushed through one jitted ``lax.scan`` per distinct prompt
length (O(1) engine steps per refill group, instead of one full-batch
decode step per prompt token) and the resulting per-request state is
scattered into the engine's batched decode state at the refilled slot
rows. Each slot carries its own decode position (``attention_decode``
accepts per-row positions), so a refilled request's cache and RoPE phases
are coherent regardless of how far other slots have decoded. Grouping by
exact length means no pad tokens ever enter the state - required for
recurrent blocks and local-attention ring buffers, where padding is not
maskable after the fact. Batch shapes are bucketed to powers of two,
bounding XLA compiles at O(log max_batch * distinct prompt lengths).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.models import lm
from repro.models.common import ModelConfig


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int = 16
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # step-level latency accounting (wall-clock seconds, perf_counter)
    t_submit: Optional[float] = None
    t_start: Optional[float] = None       # seated in a slot (prefill begins)
    t_first_token: Optional[float] = None
    t_done: Optional[float] = None

    @property
    def latency_s(self) -> Optional[float]:
        if self.t_submit is None or self.t_done is None:
            return None
        return self.t_done - self.t_submit

    @property
    def queue_wait_s(self) -> Optional[float]:
        if self.t_submit is None or self.t_start is None:
            return None
        return self.t_start - self.t_submit


@functools.partial(jax.jit, donate_argnums=0)
def _scatter_rows(state, idx, new_state):
    """``state`` with ``new_state``'s first ``len(idx)`` rows written at
    rows ``idx``. Scanned stacks carry a leading group axis, so their batch
    axis is 1; unscanned ("tail") leaves batch at axis 0. ``state`` is
    donated: a state the size of the cache is written where it lies, not
    copied beside the served weights."""
    n = idx.shape[0]

    def put(path, big, small):
        axis = 1 if any(getattr(k, "key", None) == "scan"
                        for k in path) else 0
        sel = (slice(None),) * axis + (idx,)
        rows = (slice(None),) * axis + (slice(0, n),)
        return big.at[sel].set(small[rows].astype(big.dtype))

    return dict(state, layers=jax.tree_util.tree_map_with_path(
        put, state["layers"], new_state["layers"]))


class DecodeEngine:
    """Serves ``params`` through one jitted decode step and a prefill
    program per (bucket, prompt length).

    Weight residency: at construction the engine makes the copy it serves,
    ``self.params`` (``lm.compute_params``). Every leaf the model reads
    only as ``cfg.dtype`` (attention, FFN, MoE and recurrent projections,
    ``embed``, ``lm_head``, QKV biases) is cast to ``cfg.dtype`` once, on
    the device, inside the ``engine.cast_weights`` span (attrs ``leaves``
    and ``bytes`` written; 0 when nothing needs a cast), so no launch
    re-casts a weight. Leaves the model reads in float32 (norm gains, the
    recurrent decay and gate biases) and leaves already in ``cfg.dtype``
    are the caller's own arrays. The caller's tree is never modified,
    donated or deleted."""

    def __init__(self, cfg: ModelConfig, params, *, max_batch: int = 8,
                 max_len: int = 128):
        self.cfg = cfg
        cast = [x for m, x in zip(
            jax.tree_util.tree_leaves(lm.compute_casts(params, cfg)),
            jax.tree_util.tree_leaves(params)) if m]
        with obs.profiled_span(
                "engine.cast_weights", leaves=len(cast),
                bytes=sum(x.size for x in cast)
                * jnp.dtype(cfg.dtype).itemsize):
            self.params = jax.block_until_ready(
                lm.compute_params(params, cfg))
        self.max_batch = max_batch
        self.max_len = max_len
        self.queue: List[Request] = []
        self.slots: List[Optional[Request]] = [None] * max_batch
        self.completed: List[Request] = []
        self._state = lm.init_decode_state(cfg, max_batch, max_len)
        self._toks = jnp.zeros((max_batch,), jnp.int32)
        # per-slot absolute decode position (requests start at different
        # times; attention_decode takes a position vector)
        self._slot_pos = np.zeros(max_batch, np.int32)
        # params enter every jitted call as an argument: a closed-over
        # array would be embedded in the program as a constant. The
        # function's name names the compiled module (jit_decode_step),
        # which is how a profile finds the step.

        def decode_step(params, state, tokens, pos):
            return lm.decode_step(params, cfg, state, tokens, pos)

        self._step_fn = jax.jit(decode_step)
        self._prefill_fns: Dict[Tuple[int, int], callable] = {}
        # (max_batch, vocab) logits of the last decode step, on device
        self.last_logits = None

    def submit(self, req: Request) -> None:
        req.t_submit = time.perf_counter()
        self.queue.append(req)

    # -- batched prefill ---------------------------------------------------
    @staticmethod
    def _bucket(n: int) -> int:
        return 1 << (n - 1).bit_length() if n > 1 else 1

    def _prefill_fn(self, n: int, L: int):
        """Jitted prompt prefill for ``n`` fresh requests of exact length
        ``L``: builds their decode state in one call (scan over tokens).
        ``n`` arrives bucketed to a power of two, so the compile cache
        stays O(log max_batch * distinct prompt lengths)."""
        key = (n, L)
        if key not in self._prefill_fns:
            cfg, max_len = self.cfg, self.max_len

            def prefill(params, prompts):  # prompts: (n, L) int32
                state = lm.init_decode_state(cfg, n, max_len)

                def body(carry, tok):
                    st, pos = carry
                    _, st = lm.decode_step(params, cfg, st, tok, pos)
                    return (st, pos + 1), None

                (state, _), _ = jax.lax.scan(
                    body, (state, jnp.int32(0)),
                    jnp.swapaxes(prompts, 0, 1)[:-1])
                return state

            self._prefill_fns[key] = jax.jit(prefill)   # jit_prefill
        return self._prefill_fns[key]

    def _scatter_state(self, slot_idx: List[int], new_state) -> None:
        """Write per-request decode state rows into the batched engine state
        at ``slot_idx`` (extra bucket-padding rows are dropped), in place:
        the engine's state is donated to the write."""
        self._state = _scatter_rows(self._state,
                                    jnp.asarray(slot_idx, jnp.int32),
                                    new_state)

    def _seating(self) -> Tuple[List[int], List[Request]]:
        """The free slots and the queue's head that will fill them."""
        free = [i for i, s in enumerate(self.slots) if s is None or s.done]
        return free, self.queue[:len(free)]

    def _fill_slots(self, seating=None) -> None:
        free, seat = self._seating() if seating is None else seating
        if not seat:
            return
        with obs.profiled_span("engine.refill", seated=len(seat)):
            del self.queue[:len(seat)]
            for i, req in zip(free, seat):
                req.t_start = time.perf_counter()
                self.slots[i] = req
            # one batched prefill per distinct prompt length: no pad
            # tokens ever reach the state, so recurrent layers and
            # local-attention ring buffers see exactly the prompt prefix
            # (padding could only be masked out of full-attention KV, not
            # of carried state)
            by_len: Dict[int, List[Tuple[int, Request]]] = {}
            for i, r in zip(free, seat):
                by_len.setdefault(len(r.prompt), []).append((i, r))
            toks = np.array(self._toks)
            for L, group in by_len.items():
                n = self._bucket(len(group))
                mat = np.zeros((n, L), np.int32)
                for j, (_, r) in enumerate(group):
                    mat[j] = r.prompt
                with obs.profiled_span("engine.prefill", bucket=n,
                                       prompt_len=L):
                    new_state = self._prefill_fn(n, L)(self.params,
                                                       jnp.asarray(mat))
                self._scatter_state([i for i, _ in group], new_state)
                del new_state      # free it before the next group's prefill
                for i, r in group:
                    toks[i] = r.prompt[-1]
                    # prompt prefix state covers positions 0..L-2; the
                    # last prompt token is decoded next step at its
                    # position L-1
                    self._slot_pos[i] = L - 1
            self._toks = jnp.asarray(toks)

    def step(self) -> Dict[int, int]:
        """Decode one token for every active slot; returns {rid: token}.

        Profiled spans (``engine.*``, DESIGN.md §8) split the step into
        disjoint phases under ``engine.step``: ``engine.refill`` (seating,
        prefill, state scatter), ``engine.dispatch`` (the step's launch),
        ``engine.readback`` (the host waits for the step's argmax) and
        ``engine.bookkeep`` (per-slot tokens and completion)."""
        free, seat = seating = self._seating()
        active = self.max_batch - len(free) + len(seat)
        if not active:
            return {}
        with obs.profiled_span("engine.step", active=active,
                               seated=len(seat)):
            self._fill_slots(seating)
            with obs.profiled_span("engine.dispatch"):
                # upload a copy: the transfer may read its host array
                # after this returns (jnp.array does not copy a NumPy
                # array either), and _slot_pos is bumped right after
                logits, self._state = self._step_fn(
                    self.params, self._state, self._toks,
                    jnp.asarray(self._slot_pos.copy()))
            self.last_logits = logits
            self._slot_pos += 1
            with obs.profiled_span("engine.readback"):
                nxt = np.asarray(jnp.argmax(logits, axis=-1))
            with obs.profiled_span("engine.bookkeep"):
                out = {}
                toks = np.asarray(self._toks).copy()
                now = time.perf_counter()
                for i, req in enumerate(self.slots):
                    if req is None or req.done:
                        continue
                    tok = int(nxt[i])
                    req.out.append(tok)
                    if req.t_first_token is None:
                        req.t_first_token = now
                    out[req.rid] = tok
                    toks[i] = tok
                    if len(req.out) >= req.max_new_tokens:
                        req.done = True
                        req.t_done = now
                        self.completed.append(req)
                self._toks = jnp.asarray(toks)
        return out

    def drain_completed(self) -> List[Request]:
        """Return finished requests accumulated so far and clear the list
        (fleet routers poll this between slices)."""
        done, self.completed = self.completed, []
        return done

    def run_until_done(self, max_steps: int = 1000) -> List[Request]:
        """Run until queue and slots are exhausted; returns the requests
        that completed during THIS call (a finished request whose slot was
        refilled is kept, not dropped). Earlier completions stay in the
        ``completed`` accumulator until ``drain_completed``."""
        already = len(self.completed)
        for _ in range(max_steps):
            if not self.queue and all(s is None or s.done
                                      for s in self.slots):
                break
            self.step()
        return list(self.completed[already:])
