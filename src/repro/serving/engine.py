"""Adaptive serving engine: request queue + batched greedy decode with
between-batch operator hot-swap.

The load-bearing design point: the per-layer ``(L, side, side)`` LUT
stack — ``(L, 16, 16)`` for W4A4, ``(L, 256, 256)`` for composed W8A8 —
is a *plain jitted argument* of the decode step, never a closed-over
constant.  Swapping QoS plans between batches therefore re-stacks a tiny
int32 array and changes nothing the compiler specialized on — the decode
step is traced exactly once for the whole serve, across every controller
move and library refresh (``trace_count`` pins this, and the end-to-end
test asserts it).

One ``run_batch`` call serves up to ``batch`` queued requests: prefill
walks the prompt through the *same* jitted decode step (one code path,
one trace), then greedy decode extends ``gen_len`` tokens.  Prefill and
decode are timed separately — a python-loop prefill is O(prompt) step
dispatches and would otherwise silently poison the decode throughput
number.  Between batches the engine consults the library watcher (store
changed? refresh the frontier) and the QoS controller (latency/drift
says move? swap the plan), both of which funnel through
:meth:`ServingEngine.swap_plan` and its shape/dtype validation.

Drift sampling: every ``shadow_every`` batches the final decode step is
also evaluated on copies of the caches with the *exact* LUT stack; the
mean |Δlogit| between the live and shadow step is the measured drift the
controller holds under its budget.  The shadow call reuses the one jitted
executable (same shapes, different table values).
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.approx_matmul import takes_hi_pass
from ..library.qos import (LayerPlan, plan_layer_areas, refresh_plan,
                           stack_luts, validate_lut_stack)
from ..models import decode_fn, decode_paged_fn, init_caches
from ..models.lm import prepare_mlp_weights
from ..obs.trace import current_tracer
from ..obs.trace import event as trace_event
from ..obs.trace import span as trace_span
from ..quant.int4 import PreparedWeight
from .controller import effective_load_ms
from .loadgen import LoadProfile, Request, synth_requests
from .telemetry import Telemetry

__all__ = ["BatchStats", "ServingEngine", "ContinuousServingEngine"]


def _area_hi_map(compiled) -> dict[str, float]:
    """Operator key -> glue-inclusive area upper bound over a compiled
    frontier (``CompiledLut.area_hi``; records compiled without a
    bracket collapse to their own area).  Mixed-width frontiers can
    carry one key at two widths — keeping the max keeps the value a
    sound upper bound."""
    out: dict[str, float] = {}
    for rec, comp in compiled:
        hi = getattr(comp, "area_hi", None)
        hi = rec.area if hi is None else max(rec.area, hi)
        out[rec.key] = max(out.get(rec.key, 0.0), hi)
    return out


@dataclass
class BatchStats:
    """Measurements of one served batch."""

    n_requests: int
    prefill_s: float
    decode_s: float
    prefill_tokens: int
    decode_tokens: int
    decode_steps: int
    drift: float | None = None

    @property
    def ms_per_step(self) -> float:
        return 1e3 * self.decode_s / max(1, self.decode_steps)

    @property
    def decode_tok_s(self) -> float:
        return self.decode_tokens / self.decode_s if self.decode_s else 0.0

    @property
    def prefill_tok_s(self) -> float:
        return self.prefill_tokens / self.prefill_s if self.prefill_s else 0.0


def _on_device(stack):
    """A LUT stack, or a mixed-width dict of stacks, as device arrays."""
    return ({b: jnp.asarray(a) for b, a in stack.items()}
            if isinstance(stack, dict) else jnp.asarray(stack))


def weight_codes(prepared) -> tuple[int, int]:
    """How many MLP matrices ``prepared`` (:func:`prepare_mlp_weights`)
    holds, and the bytes of its codes, scales and column sums."""
    stacks = jax.tree.leaves(prepared,
                             is_leaf=lambda a: isinstance(a, PreparedWeight))
    return (sum(p.codes.shape[0] for p in stacks),
            sum(a.nbytes for a in jax.tree.leaves(prepared)))


def wide_lut_layers(stack) -> int:
    """How many layers of a LUT stack (or of each stack of a mixed-width
    dict) carry a table for which the LUT kernel runs its second pass."""
    groups = stack.values() if isinstance(stack, dict) else (stack,)
    return sum(takes_hi_pass(t) for g in groups for t in np.asarray(g))


class ServingEngine:
    # the decode step the engine jits; the continuous engine names its own
    _decode_fn = staticmethod(decode_fn)

    def __init__(
        self,
        cfg,
        params,
        *,
        batch: int,
        prompt_len: int,
        gen_len: int,
        plan: LayerPlan | None = None,
        compiled=None,
        exact_area: float | None = None,
        sensitivities=None,
        width_map=None,
        sens_profile=None,
        warmup_caches: Callable | None = None,
    ) -> None:
        self.cfg = cfg
        self.params = params
        self.batch = int(batch)
        self.prompt_len = int(prompt_len)
        self.gen_len = int(gen_len)
        self.total = self.prompt_len + self.gen_len
        self._warmup = warmup_caches
        self._trace_count = 0
        self._last_step_args = None   # what _step last passed the step
        self.last_tokens: np.ndarray | None = None   # (n_requests, gen_len)
        # the SLO health plane (obs.health.HealthPlane), bound by serve()/
        # start(); every control-plane trace event is mirrored into it so
        # fired anomalies attribute to the exact swap/refresh/control span
        self._health = None
        # test/chaos hook: extra seconds slept inside the *timed* step
        # section — the induced-latency-spike drill flips this mid-serve
        self.inject_step_delay = 0.0

        self._adaptive = plan is not None
        self._plan = plan
        self._compiled = list(compiled) if compiled is not None else []
        self._exact_area = exact_area
        # per-layer sensitivities: a vector for uniform-width serves, a
        # {bits: vector-or-matrix} dict for mixed-width (kept for the
        # watcher's ladder rebuild)
        if isinstance(sensitivities, dict):
            self._sens = sensitivities
        else:
            self._sens = (np.ones(cfg.n_layers) if sensitivities is None
                          else np.asarray(sensitivities, dtype=np.float64))
        self._width_map = (tuple(int(b) for b in width_map)
                           if width_map is not None else None)
        # measured SensitivityProfile (optional): refresh paths re-price
        # measured cost matrices against the *refreshed* frontier through
        # it — a stale (L, O) matrix cannot follow a frontier whose
        # operator set a background fleet sweep just changed
        self._profile = sens_profile
        self._mae_by_key = {rec.key: comp.mae
                            for rec, comp in self._compiled}
        self._area_hi_by_key = _area_hi_map(self._compiled)
        # per-plan cost rows (repro.obs.costs.plan_cost_row), cached by
        # plan_id so the per-step cost attribution is a dict lookup
        self._cost_rows: dict[str, dict] = {}
        self._macs_per_layer = None
        # device-resident class stacks, keyed by ladder level and dropped
        # when the ladder object changes (see _resolve_stack)
        self._stacks: dict[int, object] = {}
        self._stacks_ladder = None

        if self._adaptive:
            assert cfg.approx_mlp, (
                "adaptive serving routes MLP matmuls through LUTs; build the "
                "config with .with_approx_mlp()"
            )
            if self._width_map is not None:
                # mixed-width: one stack per width group, the per-layer
                # width routing is a static part of the single trace
                assert len(self._width_map) == cfg.n_layers
                from ..precision.plans import (exact_mixed_stacks,
                                               stack_mixed_luts)

                stack = stack_mixed_luts(plan, self._compiled,
                                         self._width_map)
                self._luts = {b: jnp.asarray(a) for b, a in stack.items()}
                self._exact_luts = {
                    b: jnp.asarray(a)
                    for b, a in exact_mixed_stacks(self._width_map).items()}
                self.width = None
                self.widths = tuple(sorted(set(self._width_map)))
                widths = self._width_map
            else:
                stack = stack_luts(plan, self._compiled)
                self._luts = jnp.asarray(stack)
                from ..precision.widths import exact_table, width_from_stack

                # the exact shadow stack shares the live stack's width — a
                # W8A8 serve shadows against the exact 256x256 product table
                self.width = width_from_stack(self._luts)
                self.widths = (self.width.bits,)
                side = self.width.side
                self._exact_luts = jnp.asarray(np.broadcast_to(
                    exact_table("mul", self.width.bits).astype(np.int32),
                    (cfg.n_layers, side, side)).copy())
                widths = self.width.bits
            # the MLP weights' codes, scales and column sums depend only on
            # the weights and each layer's width, which no plan swap
            # changes: made once, passed to every step beside the tables
            self._prepared = prepare_mlp_weights(params, widths)
            n_codes, code_bytes = weight_codes(self._prepared)
            trace_event("serve.plan", plan=plan.plan_id,
                        lut_bits=self.lut_bits,
                        wide_lut_layers=wide_lut_layers(stack),
                        weight_codes=n_codes, weight_code_bytes=code_bytes)
        else:
            self._luts = None
            self._exact_luts = None
            self._prepared = None
            self.width = None
            self.widths = ()

        self._jit_step = jax.jit(self._make_step_fn(), donate_argnums=(1,))

    def _make_step_fn(self):
        """Build the closure the engine jits exactly once:
        ``step_fn(params, caches, *inputs, luts, prepared)`` runs the
        engine's decode function on the inputs with the plan's LUT stack
        and the MLP weights prepared for it.  An engine without a plan
        passes ``None`` for both, empty pytrees, so its program holds no
        table or code argument."""
        step = self._decode_fn(self.cfg)
        cfg, wm = self.cfg, self._width_map

        def step_fn(params, caches, *args):
            # python side effect runs once per *trace*, so this counts
            # compilations, not calls — the no-retrace-across-swaps
            # invariant is `trace_count == 1` after any number of swaps
            self._trace_count += 1
            *inputs, luts, prepared = args
            if luts is None:   # the encoder-decoder step takes no tables
                return step(cfg, params, caches, *inputs)
            return step(cfg, params, caches, *inputs, luts=luts,
                        width_map=wm, prepared=prepared)
        return step_fn

    # ----------------------------------------------------------------- state
    @property
    def trace_count(self) -> int:
        """How many times the decode step has been traced (must stay 1)."""
        return self._trace_count

    @property
    def lut_bits(self) -> int | str:
        """The LUT operand width the plan serves (4 or 8); a mixed-width
        plan gives its widths joined, as ``"4+8"``."""
        return (self.widths[0] if len(self.widths) == 1
                else "+".join(map(str, self.widths)))

    @property
    def plan(self) -> LayerPlan | None:
        return self._plan

    def _step(self, caches, *inputs, luts=None):
        """The jitted step on ``inputs`` with ``luts`` (default: the live
        stack) and the prepared MLP weights."""
        self._last_step_args = (self.params, caches, *inputs,
                                self._luts if luts is None else luts,
                                self._prepared)
        return self._jit_step(*self._last_step_args)

    # ------------------------------------------------------------------ swap
    def swap_plan(self, plan: LayerPlan, stack, *, reason: str = "manual",
                  telemetry: Telemetry | None = None,
                  batch_idx: int = 0) -> bool:
        """Adopt a new plan between batches.  Validates the stack against
        the live one (shape/dtype — a mismatch would retrace), suppresses
        no-op swaps (same per-layer assignment), logs the swap.  Returns
        whether the plan actually changed."""
        assert self._adaptive, "engine was built without a QoS plan"
        if plan.plan_id == self._plan.plan_id:
            return False
        new = _on_device(stack)
        validate_lut_stack(self._luts, new)
        old_id = self._plan.plan_id
        self._plan, self._luts = plan, new
        if telemetry is not None:
            telemetry.register_plan(plan)
            telemetry.record_swap(batch=batch_idx, reason=reason,
                                  old=old_id, new=plan.plan_id)
        eid = trace_event("serve.swap", reason=reason, batch=batch_idx,
                          old=old_id, new=plan.plan_id,
                          lut_bits=self.lut_bits,
                          wide_lut_layers=wide_lut_layers(stack))
        if self._health is not None:
            self._health.note_event("serve.swap", step=batch_idx,
                                    event_id=eid, reason=reason,
                                    old=old_id, new=plan.plan_id)
        return True

    def refresh(self, frontier, *, controller=None, scheduler=None,
                telemetry: Telemetry | None = None,
                batch_idx: int = 0) -> bool:
        """Adopt a refreshed frontier in the form
        :meth:`~repro.serving.watcher.LibraryWatcher.load_frontier` gives
        it: a :class:`~repro.precision.plans.MixedFrontier` for a
        mixed-width engine (:meth:`refresh_mixed`), else ``(compiled,
        exact_area, bits)`` (:meth:`refresh_library`)."""
        kw = dict(controller=controller, scheduler=scheduler,
                  telemetry=telemetry, batch_idx=batch_idx)
        if self._width_map is not None:
            return self.refresh_mixed(frontier, **kw)
        compiled, exact_area, _bits = frontier
        return self.refresh_library(compiled, exact_area, **kw)

    def refresh_library(self, compiled, exact_area: float, *,
                        controller=None, scheduler=None,
                        reason: str = "library",
                        telemetry: Telemetry | None = None,
                        batch_idx: int = 0) -> bool:
        """Adopt a refreshed frontier (the watcher path).  With a
        controller (or class scheduler), its ladder is rebuilt and the
        current level re-stacked; without either, the live plan's budget
        re-selects over the new frontier via
        :func:`repro.library.qos.refresh_plan`.

        Nothing — engine frontier, controller ladder — is mutated until the
        new stack passes :func:`~repro.library.qos.validate_lut_stack`
        inside :meth:`swap_plan`: a surprising store merge (e.g. a future
        8-bit frontier landing in a watched 4-bit store) raises and leaves
        the runtime serving consistently on the old plan."""
        # with a measured profile, re-price the refreshed frontier (a
        # stale (L, O) matrix cannot index new operator columns); without
        # one, the ladder keeps its own sensitivity model as before
        new_sens = self._uniform_sens(compiled)
        if controller is not None or scheduler is not None:
            owner = (controller.ladder if controller is not None
                     else scheduler.ladder)
            new_ladder = owner.refresh(compiled, exact_area,
                                       sensitivities=new_sens)
            level = (min(controller.level, len(new_ladder) - 1)
                     if controller is not None else 0)
            plan, stack = new_ladder.plan(level), new_ladder.luts(level)
        else:
            new_ladder = level = None
            plan = refresh_plan(
                self._plan, compiled,
                self._sens if new_sens is None else new_sens,
                exact_area=exact_area)
            stack = stack_luts(plan, compiled)
        changed = self.swap_plan(plan, stack, reason=reason,
                                 telemetry=telemetry, batch_idx=batch_idx)
        self._compiled = list(compiled)
        self._mae_by_key = {rec.key: comp.mae for rec, comp in self._compiled}
        self._area_hi_by_key = _area_hi_map(self._compiled)
        self._cost_rows = {}
        self._exact_area = exact_area
        if controller is not None:
            controller.adopt(new_ladder, level=level)
        if scheduler is not None:
            scheduler.adopt(new_ladder)
        return changed

    def refresh_mixed(self, mixed, *, controller=None, scheduler=None,
                      reason: str = "library",
                      telemetry: Telemetry | None = None,
                      batch_idx: int = 0) -> bool:
        """The mixed-width watcher path: rebuild the plan ladder over a
        refreshed :class:`~repro.precision.plans.MixedFrontier` *inside*
        the frozen width map, then re-point the controller and the class
        scheduler at it.  Group shapes are fixed by the width map, so the
        new level stacks validate against the live ones by construction —
        and are checked anyway before anything is adopted."""
        from ..precision.plans import (build_mixed_ladder,
                                       mixed_cost_matrix, stack_mixed_luts)

        assert self._width_map is not None, "engine serves a uniform width"
        sens = self._mixed_sens(mixed)
        old = (controller.ladder if controller is not None
               else scheduler.ladder if scheduler is not None else None)
        if old is None:
            # plain mixed serve (no controller / classes): the analog of
            # the refresh_plan path — re-select the live plan's budget
            # inside the frozen width map and keep serving
            wm = np.asarray(self._width_map)
            plan = refresh_plan(
                self._plan, mixed.compiled,
                mixed_cost_matrix(mixed, sens, len(wm)),
                exact_area=mixed.exact_areas(self._width_map),
                allowed=mixed.op_bits[None, :] == wm[:, None])
            stack = stack_mixed_luts(plan, mixed.compiled, self._width_map)
        else:
            new_ladder = build_mixed_ladder(
                mixed, self._width_map, sens,
                levels=old.requested_levels)
            level = (min(controller.level, len(new_ladder) - 1)
                     if controller is not None else 0)
            plan, stack = new_ladder.plan(level), new_ladder.luts(level)
        changed = self.swap_plan(plan, stack, reason=reason,
                                 telemetry=telemetry, batch_idx=batch_idx)
        self._compiled = list(mixed.compiled)
        self._mae_by_key = {rec.key: comp.mae for rec, comp in self._compiled}
        self._area_hi_by_key = _area_hi_map(self._compiled)
        self._cost_rows = {}
        if old is not None and controller is not None:
            controller.adopt(new_ladder, level=level)
        if old is not None and scheduler is not None:
            scheduler.adopt(new_ladder)
        return changed

    def _uniform_sens(self, compiled):
        """Measured pricing for a refreshed uniform-width frontier, or
        ``None`` when there is no profile (the caller keeps its own
        sensitivity model)."""
        if self._profile is None:
            return None
        from ..sensitivity.profile import costs_for

        return costs_for(self._profile, self.width.bits, compiled,
                         self.cfg.n_layers)

    def _mixed_sens(self, mixed):
        """Per-width pricing for a refreshed mixed frontier: measured via
        the profile when present, else the constructor's sensitivity
        model (vectors follow any frontier; a caller-supplied measured
        matrix cannot, and the resulting ValueError makes the watcher
        skip the refresh)."""
        if self._profile is None:
            return self._sens
        from ..sensitivity.profile import costs_for

        return {bits: costs_for(self._profile, bits, fr.compiled,
                                self.cfg.n_layers)
                for bits, fr in mixed.by_width.items()}

    def _plan_maes(self, plan: LayerPlan) -> np.ndarray:
        """Per-layer operator mae of a plan (0 for exact layers) — the
        attribution vector the online sensitivity estimator consumes."""
        return np.array([0.0 if c.key is None
                         else self._mae_by_key.get(c.key, 0.0)
                         for c in plan.choices])

    def _resolve_stack(self, active_classes):
        """The LUT stack of a batch or step: with a scheduler, it decodes
        at the level of its *strictest* active class (slots share one
        step, so the most exacting tenant sets the table for everyone in
        it — per-class plans separate again at the router's replica
        level).  Returns ``(luts, plan, global_level, step_level)`` — the
        last is the level the step actually decodes at, which the
        provenance ledger records per token range.  The stack comes from
        a device-resident cache per ladder level, cleared when the ladder
        object changes (a refresh): without it every class batch or step
        would re-upload its ``(n_layers, side, side)`` stack."""
        if not self._adaptive:
            return None, None, None, None
        if self._scheduler is None:
            lvl = self._controller.level if self._controller else None
            return None, self._plan, lvl, lvl
        sch = self._scheduler
        glevel = (self._controller.level if self._controller is not None
                  else sch.top_level)
        level = min((sch.level_for(c, glevel) for c in active_classes),
                    default=min(glevel, sch.top_level))
        if sch.ladder is not self._stacks_ladder:
            self._stacks.clear()
            self._stacks_ladder = sch.ladder
        luts = self._stacks.get(level)
        if luts is None:
            luts = self._stacks[level] = _on_device(sch.ladder.luts(level))
        plan = sch.ladder.plan(level)
        self.telemetry.register_plan(plan)
        return luts, plan, glevel, level

    # --------------------------------------------------------- control plane
    def _bind(self, *, telemetry, controller, watcher, scheduler, online,
              health, log) -> Telemetry:
        """Bind the control plane a serve runs under."""
        if scheduler is not None:
            assert self._adaptive, "class-aware serving needs a QoS plan"
        self.telemetry = telemetry or Telemetry()
        self._controller, self._watcher = controller, watcher
        self._scheduler, self._online, self._log = scheduler, online, log
        self._health = health
        if self._adaptive:
            self.telemetry.register_plan(self._plan)
        return self.telemetry

    def _control_plane(self, idx: int, unit: str, load_ms: float,
                       drift: Callable[[], float | None], glevel) -> None:
        """What runs after batch or step ``idx`` (``unit`` names which):
        the watcher poll and library refresh, then the controller, fed the
        loop's load signal ``load_ms`` and ``drift()``.  ``drift`` is read
        after the refresh and gives the measured drift only when it speaks
        for the global level ``glevel``, else ``None``."""
        if not self._adaptive:
            return
        controller, scheduler = self._controller, self._scheduler
        health, log = self._health, self._log
        if self._watcher is not None and self._watcher.poll():
            try:
                # LookupError: store emptied; ValueError: refreshed stack
                # would retrace (validate_lut_stack refused).  Either way
                # the server keeps running on the old, still-consistent
                # plan.
                changed = self.refresh(
                    self._watcher.load_frontier(), controller=controller,
                    scheduler=scheduler, telemetry=self.telemetry,
                    batch_idx=idx)
                eid = trace_event("serve.refresh", cause="watcher",
                                  changed=changed, batch=idx)
                if health is not None:
                    health.note_event("serve.refresh", step=idx,
                                      event_id=eid, changed=changed)
                if changed and log:
                    log(f"{unit} {idx}: library refresh -> "
                        f"plan {self._plan.plan_id}")
            except (LookupError, ValueError) as e:
                trace_event("serve.refresh", cause="watcher", changed=False,
                            batch=idx, skipped=str(e))
                if log:
                    log(f"watcher: refresh skipped ({e})")
        if controller is None:
            return
        level = controller.observe(load_ms, drift())
        if level is None:
            return
        reason = controller.last_reason
        eid = trace_event("serve.control", level=level, cause=reason,
                          batch=idx)
        if health is not None:
            health.note_event("serve.control", step=idx, event_id=eid,
                              level=level, cause=reason)
        if scheduler is None:
            moved = self.swap_plan(controller.plan, controller.luts(),
                                   reason=f"qos-{reason}",
                                   telemetry=self.telemetry, batch_idx=idx)
            if moved and log:
                log(f"{unit} {idx}: controller -> level {level} ({reason}), "
                    f"plan {self._plan.plan_id}")
        else:
            # the global operating point moved; per-class stacks resolve
            # against it at their next batch or step.  glevel was read
            # before a possible ladder refresh above — clamp both levels to
            # the ladder the swap log points at
            lad = scheduler.ladder
            self.telemetry.record_swap(
                batch=idx, reason=f"qos-{reason}",
                old=lad.plan(min(glevel, len(lad) - 1)).plan_id,
                new=lad.plan(min(level, len(lad) - 1)).plan_id)
            if log:
                log(f"{unit} {idx}: controller -> global level {level} "
                    f"({reason})")

    # ----------------------------------------------------------------- batch
    def run_batch(self, requests: list[Request], *,
                  shadow: bool = False, luts=None) -> BatchStats:
        """Serve one batch: prefill the prompts, greedily decode
        ``gen_len`` tokens.  Short batches are zero-padded to the fixed
        batch size so every call reuses the single traced executable.

        ``luts`` overrides the engine's live stack for this batch only —
        the class-aware serve passes each batch its QoS class's plan
        stack (same shapes, so still the one trace)."""
        assert 0 < len(requests) <= self.batch
        if luts is not None:
            luts = _on_device(luts)
        prompts_np = np.zeros((self.batch, self.prompt_len), np.int32)
        for i, r in enumerate(requests):
            # heterogeneous prompt lengths zero-pad to the fixed geometry:
            # the fixed-batch engine pays max-length for every request,
            # which is exactly the cost paged continuous batching removes
            assert len(r.tokens) <= self.prompt_len, (
                f"request {r.rid} prompt ({len(r.tokens)}) exceeds engine "
                f"prompt_len ({self.prompt_len})")
            prompts_np[i, :len(r.tokens)] = r.tokens
        prompts = jnp.asarray(prompts_np)

        caches = init_caches(self.cfg, self.batch, self.total)
        if self._warmup is not None:
            caches = self._warmup(caches)

        with trace_span("serve.batch", n_requests=len(requests)) as batch_sp:
            with trace_span("serve.prefill",
                            tokens=len(requests) * self.prompt_len):
                t0 = time.perf_counter()
                logits = None
                for t in range(self.prompt_len):
                    logits, caches = self._step(caches, prompts[:, t:t + 1],
                                                jnp.int32(t), luts=luts)
                logits.block_until_ready()
                t1 = time.perf_counter()

            shadow_logits = None
            shadow_s = 0.0
            generated = []
            with trace_span("serve.decode", steps=self.gen_len) as decode_sp:
                for t in range(self.prompt_len, self.total):
                    tok = jnp.argmax(logits, axis=-1)[:, None]
                    tok = tok.astype(jnp.int32)
                    generated.append(tok)
                    if shadow and self._adaptive and t == self.total - 1:
                        # exact shadow step on copies — the live call below
                        # donates the real caches, the copies are consumed by
                        # the shadow.  Timed separately and excluded from
                        # decode_s: the shadow is measurement overhead, and
                        # folding it into ms/step would bias the very latency
                        # signal the controller acts on.
                        with trace_span("serve.shadow"):
                            ts = time.perf_counter()
                            shadow_caches = jax.tree.map(jnp.copy, caches)
                            shadow_logits, _ = self._step(
                                shadow_caches, tok, jnp.int32(t),
                                luts=self._exact_luts)
                            shadow_logits.block_until_ready()
                            shadow_s = time.perf_counter() - ts
                    logits, caches = self._step(caches, tok, jnp.int32(t),
                                                luts=luts)
                logits.block_until_ready()
                t2 = time.perf_counter()
                decode_sp.set(shadow_s=round(shadow_s, 6))

            n = len(requests)
            drift = None
            if shadow_logits is not None:
                # only the real rows: zero-padded requests decode garbage and
                # would contaminate the controller's drift signal on the
                # partial batches ramp/spike load produces routinely
                drift = float(jnp.abs(logits[:n] - shadow_logits[:n]).mean())
            stats = BatchStats(
                n_requests=n,
                prefill_s=t1 - t0,
                decode_s=t2 - t1 - shadow_s,
                prefill_tokens=n * self.prompt_len,
                decode_tokens=n * self.gen_len,
                decode_steps=self.gen_len,
                drift=drift,
            )
            batch_sp.set(ms_per_step=round(stats.ms_per_step, 3),
                         decode_tok_s=round(stats.decode_tok_s, 2))
            if drift is not None:
                batch_sp.set(drift=round(drift, 6))
        # completions for the real (unpadded) requests — a degenerate
        # repeated-token sample is also the quickest eyeball check that an
        # aggressive plan's LUT routing is live in decode
        self.last_tokens = np.asarray(jnp.concatenate(generated, axis=1))[:n]
        return stats

    # ----------------------------------------------------------------- serve
    def serve(
        self,
        profile: LoadProfile,
        *,
        controller=None,
        watcher=None,
        scheduler=None,
        online=None,
        telemetry: Telemetry | None = None,
        seed: int = 0,
        on_batch_end: Callable[["ServingEngine", int], None] | None = None,
        log: Callable[[str], None] | None = None,
        health=None,
    ) -> Telemetry:
        """Run the full serving loop over a synthetic load profile.

        Each tick's arrivals join the queue; the queue drains in batches
        of up to ``batch`` requests.  With a class ``scheduler``
        (:class:`repro.sensitivity.classes.ClassScheduler`) there is one
        queue per declared QoS class, drained in priority order, and each
        batch decodes on *its class's* plan stack — same shapes, same
        single trace, but ``gold`` rides a more exact level than
        ``batch``.  After every batch the control plane runs: watcher
        poll (library refresh), per-class drift bookkeeping, online
        sensitivity update, controller observe (global level move), then
        the optional ``on_batch_end`` hook (tests use it to mutate the
        store mid-serve)."""
        assert profile.prompt_len == self.prompt_len
        assert profile.gen_len == self.gen_len
        telemetry = self._bind(telemetry=telemetry, controller=controller,
                               watcher=watcher, scheduler=scheduler,
                               online=online, health=health, log=log)
        per_tick = synth_requests(profile, self.cfg.vocab_size, seed)
        queue: deque[Request] = deque()
        queues: dict[str, deque[Request]] | None = None
        if scheduler is not None:
            queues = {name: deque() for name in scheduler.book.names}
        # wall-clock enqueue times (requests themselves carry only the
        # synthetic arrival tick) so drained batches can report real
        # time-in-queue to the per-class wait histograms
        enqueued_at: dict[int, float] = {}
        batch_idx = 0
        for tick in range(profile.n_ticks):
            now = time.perf_counter()
            for r in per_tick[tick]:
                enqueued_at[r.rid] = now
                if queues is not None:
                    queues[scheduler.book.route(r.qos_class)].append(r)
                else:
                    queue.append(r)
            while True:
                # ---- next batch: priority class queue, or the one queue
                if queues is not None:
                    cls = next((n for n in scheduler.book.names
                                if queues[n]), None)
                    if cls is None:
                        break
                    q = queues[cls]
                else:
                    if not queue:
                        break
                    cls, q = None, queue
                reqs = [q.popleft() for _ in range(min(self.batch, len(q)))]
                backlog = (sum(len(x) for x in queues.values())
                           if queues is not None else len(queue))
                t_drain = time.perf_counter()
                telemetry.record_queue(
                    cls, backlog,
                    [t_drain - enqueued_at.pop(r.rid, t_drain)
                     for r in reqs])

                # ---- resolve this batch's plan --------------------------
                if scheduler is not None:
                    luts_b, plan_b, glevel, level_c = self._resolve_stack(
                        [cls])
                else:
                    glevel = level_c = None
                    plan_b, luts_b = self._plan, None

                # per-class cadence first (it counts the batch), then the
                # controller's global cadence — no short-circuit, so a
                # class's sampling never aliases with the drain order
                sched_want = (scheduler is not None
                              and scheduler.wants_shadow(cls))
                ctrl_want = (controller is not None
                             and controller.wants_shadow(batch_idx))
                want_shadow = self._adaptive and (sched_want or ctrl_want)
                stats = self.run_batch(reqs, shadow=want_shadow, luts=luts_b)
                telemetry.record_batch(
                    batch=batch_idx, tick=tick, n_requests=stats.n_requests,
                    prefill_s=stats.prefill_s, decode_s=stats.decode_s,
                    prefill_tokens=stats.prefill_tokens,
                    decode_tokens=stats.decode_tokens,
                    decode_steps=stats.decode_steps,
                    plan_id=plan_b.plan_id if self._adaptive else None,
                    drift=stats.drift, backlog=backlog, qos_class=cls,
                )
                if stats.drift is not None and self._adaptive:
                    if scheduler is not None:
                        scheduler.observe(cls, stats.drift)
                    if online is not None:
                        online.update(self._plan_maes(plan_b), stats.drift)
                if health is not None:
                    health.observe_step(
                        step=batch_idx, step_ms=stats.ms_per_step,
                        classes={cls: {}} if cls is not None else {},
                        drift=stats.drift, backlog=backlog,
                        plan_id=plan_b.plan_id if self._adaptive else None,
                        level=glevel,
                        class_state=(scheduler.snapshot(glevel)
                                     if scheduler is not None else None))

                # ---- between-batch control plane ------------------------
                self._control_plane(
                    batch_idx, "batch",
                    # the load signal is *effective* ms/step: service time
                    # scaled by outstanding work (Little's-law flavour) —
                    # raw step latency is nearly plan-independent, so a
                    # building queue, not the step clock, is what says
                    # "trade accuracy for throughput" under ramp/spike load
                    effective_load_ms(stats.ms_per_step, backlog=backlog,
                                      capacity=self.batch),
                    # with classes, the batch may have decoded below the
                    # global level (its class cap) — its drift then says
                    # nothing about the global operating point
                    lambda: (stats.drift
                             if scheduler is None or level_c == glevel
                             else None),
                    glevel)
                if on_batch_end is not None:
                    on_batch_end(self, batch_idx)
                batch_idx += 1
        return telemetry


class ContinuousServingEngine(ServingEngine):
    """Continuous batching over a fixed pool of decode slots.

    The fixed-batch loop above admits requests only at batch boundaries:
    an arrival one step after a batch starts waits out the whole batch,
    and every slot reserves a full-length KV cache.  This engine decodes
    token-at-a-time over ``max_slots`` slots — requests join and leave
    the running batch *per step* through an active-mask, KV lives in a
    paged pool (:mod:`repro.serving.kvcache`), and prefill is just the
    first ``len(prompt)-1`` steps of a slot's life through the *same*
    jitted step.  All step inputs (``tok``, ``pos``, ``active``,
    ``tables``, the LUT stack) are plain jitted arguments with fixed
    shapes, so the one-trace contract carries over verbatim: joins,
    leaves, preemptions and plan swaps re-stack host arrays and never
    retrace (``trace_count`` stays 1).

    Latency SLOs: a :class:`~repro.sensitivity.classes.QoSClass` that
    declares ``slo_ms`` (e.g. ``gold:0.02@8ms``) is entitled to a slot —
    when the pool is full, its arrivals preempt the worst lower-tier
    slot.  The victim keeps its pages (its paged KV survives untouched;
    sliding-window ring rows are snapshotted host-side) and resumes from
    the head of its class queue, so preemption costs a suspension, never
    a re-prefill.  Admission itself drains the class queues weighted-
    fair (:class:`~repro.serving.slots.WeightedFairQueues`) instead of
    strictly by priority.
    """

    _decode_fn = staticmethod(decode_paged_fn)
    # class-level defaults so the provenance/cost bookkeeping helpers stay
    # drivable on a bare instance (tests exercise them without __init__)
    replica_name = ""
    _area_hi_by_key: dict[str, float] = {}
    _macs_per_layer = None

    def __init__(self, cfg, params, *, max_slots: int, prompt_len: int,
                 gen_len: int, page_size: int = 8, n_pages: int | None = None,
                 steps_per_tick: int | None = None, **kw) -> None:
        from ..models import init_paged_caches  # validates the family

        assert kw.pop("warmup_caches", None) is None, (
            "continuous batching serves LM families only")
        self.max_slots = int(max_slots)
        self.page_size = int(page_size)
        total = int(prompt_len) + int(gen_len)
        pages_per_req = -(-total // self.page_size)
        # default pool: every slot can hold a worst-case request PLUS one
        # spare slot's worth — preempted victims keep their pages, so
        # without headroom an SLO arrival into a full pool could never
        # allocate and preemption would be permanently page-blocked.
        # Under-provisioned regimes (admission actually blocking) pass
        # n_pages explicitly.
        self.n_pages = ((self.max_slots + 1) * pages_per_req
                        if n_pages is None else int(n_pages))
        self.table_entries = pages_per_req
        self.steps_per_tick = (int(steps_per_tick) if steps_per_tick
                               else max(1, int(gen_len)))
        self._init_paged_caches = init_paged_caches
        # the router stamps its replica name here so every req.* lifecycle
        # event names the engine that actually served the request
        self.replica_name = ""
        super().__init__(cfg, params, batch=max_slots, prompt_len=prompt_len,
                         gen_len=gen_len, **kw)
        self._started = False

    def lowered_step(self):
        """The decode step lowered (not compiled) at the types of the
        arguments :meth:`step_once` last passed it: the program the device
        ran, for checks of which kernels it holds.  Reuses that trace."""
        assert self._last_step_args is not None, \
            "run a step before lowered_step()"
        # the caches were donated to the step, so only their types remain
        types = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=a.sharding),
            self._last_step_args)
        return self._jit_step.lower(*types)

    # ----------------------------------------------------------------- state
    @property
    def occupancy(self) -> float:
        return self._pool.occupancy if self._started else 0.0

    @property
    def queue_depth(self) -> int:
        return self._queues.depth if self._started else 0

    @property
    def idle(self) -> bool:
        return (not self._started
                or (self._pool.n_active == 0 and self._queues.depth == 0))

    @property
    def load_score(self) -> float:
        """Router's routing signal: active + queued work per slot."""
        if not self._started:
            return 0.0
        return (self._pool.n_active + self._queues.depth) / self.max_slots

    @property
    def preemption_count(self) -> int:
        return self._n_preemptions

    # ----------------------------------------------------------------- setup
    def start(self, *, telemetry: Telemetry | None = None, controller=None,
              watcher=None, scheduler=None, online=None,
              shadow_every: int | None = None, health=None, provenance=None,
              log: Callable[[str], None] | None = None) -> Telemetry:
        """Bind the control plane and reset all serving state (slots,
        pages, queues, caches).  Callable directly (the router drives
        replicas through ``submit``/``step_once``) or via :meth:`serve`."""
        from .kvcache import PageAllocator
        from .slots import SlotPool, WeightedFairQueues

        self._bind(telemetry=telemetry, controller=controller,
                   watcher=watcher, scheduler=scheduler, online=online,
                   health=health, log=log)
        if shadow_every is not None:
            self._shadow_every = max(1, int(shadow_every))
        elif controller is not None:
            self._shadow_every = max(1, controller.config.shadow_every)
        elif scheduler is not None:
            self._shadow_every = scheduler.shadow_every
        else:
            self._shadow_every = 4
        self._alloc = PageAllocator(self.n_pages, self.page_size)
        self._caches = self._init_paged_caches(
            self.cfg, self.max_slots, self.n_pages, self.page_size,
            self.total)
        self._pool = SlotPool(self.max_slots)
        if scheduler is not None:
            self._queues = WeightedFairQueues(
                scheduler.book.names, scheduler.book.drain_weights())
        else:
            self._queues = WeightedFairQueues(("std",))
        self._step_idx = 0
        self._tick = 0
        self._n_preemptions = 0
        self.completions: dict[int, np.ndarray] = {}
        # approximation-provenance ledger: when tracing is configured the
        # ledger rides in the trace dir (one shared writer per process, so
        # router replicas never collide); tests may inject their own
        self._provenance = provenance
        if self._provenance is None:
            tr = current_tracer()
            if tr is not None:
                from ..obs.provenance import ledger_for

                self._provenance = ledger_for(tr.root, tr.tag)
        self._prov_open: dict[int, dict] = {}
        # cost plane: the model's LUT-routable MAC vector prices every
        # provenance range; families that never route (RWKV) serve with
        # the cost plane off
        from ..obs.costs import mlp_macs_per_layer

        try:
            self._macs_per_layer = mlp_macs_per_layer(self.cfg)
        except ValueError:
            self._macs_per_layer = None
        self._cost_rows = {}
        if self._provenance is not None and self._macs_per_layer is not None:
            self._provenance.note_model(name=self.cfg.name,
                                        macs=self._macs_per_layer)
        self._started = True
        return self.telemetry

    # ------------------------------------------------------------- admission
    def submit(self, request: Request, now: float | None = None) -> None:
        """Queue one request.  Join/leave happens per decode step, so this
        never blocks; admission itself waits for a slot *and* pages."""
        assert self._started, "call start() before submit()"
        assert len(request.tokens) <= self.prompt_len, (
            f"request {request.rid} prompt ({len(request.tokens)}) exceeds "
            f"engine prompt_len ({self.prompt_len})")
        from .slots import SeqState

        cls = (self._scheduler.book.route(request.qos_class)
               if self._scheduler is not None else "std")
        now = time.perf_counter() if now is None else now
        self._queues.push(cls, SeqState(
            rid=request.rid, cls=cls,
            prompt=np.asarray(request.tokens, np.int32),
            gen_len=self.gen_len, submitted_t=now))
        self._req_event("req.queued", rid=request.rid, cls=cls,
                        prompt_len=len(request.tokens))

    def _req_event(self, name: str, **attrs) -> str:
        """One request-lifecycle trace event; no-op when tracing is off.
        Every serving-layer event with a request in scope carries its
        ``rid`` (and the replica name under a router) so the obs side can
        reconstruct the causal chain per request."""
        if self.replica_name:
            attrs["replica"] = self.replica_name
        return trace_event(name, **attrs)

    def _admissible(self, seq) -> bool:
        # a preempted request still holds its pages; a fresh one needs the
        # pool to cover its whole prompt+gen lifetime (out-of-pages blocks
        # admission up front, it never corrupts a running neighbour)
        return self._alloc.holds(seq.rid) or self._alloc.can_alloc(
            seq.n_tokens)

    def _place(self, idx: int, seq, now: float) -> None:
        if not self._alloc.holds(seq.rid):
            self._alloc.alloc(seq.rid, seq.n_tokens)
        if seq.ring_rows is not None:
            # restore the suspended request's sliding-window ring rows
            # into its new slot (paged layers need nothing: the page
            # tables re-point at the same physical pages)
            for li, rows in seq.ring_rows.items():
                layer = self._caches[li]
                self._caches[li] = {
                    k: layer[k].at[idx].set(jnp.asarray(v))
                    for k, v in rows.items()}
            seq.ring_rows = None
        cls = seq.cls if self._scheduler is not None else None
        if seq.suspended_at is not None:
            # resume path: close out the suspension and say so — both as
            # a req.* chain link and as a serve.resume *control* event,
            # so an anomaly right after a resume attributes to the
            # resume, not to some stale earlier swap
            susp = now - seq.suspended_at
            seq.suspended_at = None
            seq.suspended_s += susp
            if seq.first_token_t is None:
                seq.suspended_before_first_s += susp
            self.telemetry.record_suspension(cls, susp)
            self._req_event("req.resume", rid=seq.rid, cls=seq.cls,
                            slot=idx, suspended_ms=round(1e3 * susp, 3))
            eid = trace_event("serve.resume", step=self._step_idx,
                              rid=seq.rid, cls=seq.cls)
            if self._health is not None:
                self._health.note_event("serve.resume", step=self._step_idx,
                                        event_id=eid, rid=seq.rid,
                                        cls=seq.cls)
        elif seq.admitted_t is None:
            seq.admitted_t = now
            seq.queue_wait_s = now - seq.submitted_t
            self.telemetry.record_queue(cls, self._queues.depth,
                                        [seq.queue_wait_s])
            self._req_event("req.admitted", rid=seq.rid, cls=seq.cls,
                            slot=idx,
                            queue_ms=round(1e3 * seq.queue_wait_s, 3))
            self._req_event("req.prefill", rid=seq.rid, cls=seq.cls,
                            slot=idx, prompt_len=len(seq.prompt))
        self._pool.place(idx, seq)

    def _preempt_slot(self, idx: int, by_cls: str, now: float) -> None:
        seq = self._pool.evict(idx)
        rows: dict[int, dict] = {}
        for li, layer in enumerate(self._caches):
            if "k" in layer:    # per-slot ring (sliding-window attention)
                rows[li] = {"k": np.asarray(layer["k"][idx]),
                            "v": np.asarray(layer["v"][idx])}
        seq.ring_rows = rows
        seq.preempted += 1
        seq.suspended_at = now
        self._n_preemptions += 1
        self._queues.push_front(seq.cls, seq)
        self._prov_close(seq.rid)
        self.telemetry.record_preemption(
            step=self._step_idx, victim_rid=seq.rid, victim_class=seq.cls,
            by_class=by_cls)
        self._req_event("req.preempt", rid=seq.rid, cls=seq.cls,
                        step=self._step_idx, by=by_cls)
        eid = trace_event("serve.preempt", step=self._step_idx, rid=seq.rid,
                          victim=seq.cls, by=by_cls)
        if self._health is not None:
            self._health.note_event("serve.preempt", step=self._step_idx,
                                    event_id=eid, rid=seq.rid,
                                    victim=seq.cls, by=by_cls)
        if self._log:
            self._log(f"step {self._step_idx}: preempt rid={seq.rid} "
                      f"({seq.cls}) for {by_cls}")

    def _admit(self, now: float) -> None:
        # 1) weighted-fair fill of free slots
        while (idx := self._pool.free_slot()) is not None:
            picked = self._queues.pick(self._admissible)
            if picked is None:
                break
            _, seq = picked
            self._place(idx, seq, now)
        # 2) SLO preemption: a queued request whose class declares a
        # latency SLO claims a slot from the worst strictly-lower tier
        if self._scheduler is None:
            return
        book = self._scheduler.book
        for _ in range(self.max_slots):
            if self._pool.free_slot() is not None:
                break
            did = False
            for c in book:
                if c.slo_ms is None:
                    continue
                head = self._queues.peek(c.name)
                if head is None or not self._admissible(head):
                    continue
                victim = self._pool.pick_victim(
                    lambda n: book.get(n).priority, c.priority)
                if victim is None:
                    continue
                self._preempt_slot(victim, by_cls=c.name, now=now)
                self._place(victim, self._queues.pop(c.name), now)
                did = True
                break
            if not did:
                break

    # ------------------------------------------------------------- provenance
    def _prov_extend(self, seq, token_idx: int, plan_b, level) -> None:
        """Charge one generated token to the active plan: extend the
        request's open decode-step range when the plan is unchanged and
        contiguous, else seal it and open a new one.  Ranges also seal on
        preemption and completion, so a finished request's ranges tile
        ``[0, gen_len)`` exactly — the gap-free audit the provenance CLI
        gates on."""
        pid = plan_b.plan_id if plan_b is not None else "exact"
        r = self._prov_open.get(seq.rid)
        if r is not None and r["plan"] == pid and r["t1"] == token_idx:
            r["t1"] = token_idx + 1
            return
        if r is not None:
            self._provenance.record_range(**r)
        if plan_b is not None:
            # plans missing an exact_area (stub plans in direct-drive
            # tests) stay unpriced; the cost audit flags them
            exact_area = getattr(plan_b, "exact_area", None)
            areas = (plan_layer_areas(plan_b, self._area_hi_by_key)
                     if exact_area is not None else None)
            self._provenance.note_plan(
                plan_b.plan_id, [c.key or "exact" for c in plan_b.choices],
                width_map=self._width_map,
                areas=[lo for lo, _ in areas] if areas else None,
                areas_hi=[hi for _, hi in areas] if areas else None,
                exact_area=exact_area)
        self._prov_open[seq.rid] = {
            "rid": seq.rid, "cls": seq.cls, "t0": token_idx,
            "t1": token_idx + 1, "plan": pid, "level": level, "drift": [],
            "replica": self.replica_name or None}

    def _prov_close(self, rid: int) -> None:
        if self._provenance is None:
            return
        r = self._prov_open.pop(rid, None)
        if r is not None:
            self._provenance.record_range(**r)

    def _cost_row(self, plan_b) -> dict:
        """The per-token cost increments of the step's live plan, cached
        by plan id (refresh paths invalidate — areas can move when a
        background sweep lands a new frontier)."""
        pid = plan_b.plan_id if plan_b is not None else "exact"
        row = self._cost_rows.get(pid)
        if row is None:
            from ..obs.costs import plan_cost_row

            areas = (plan_layer_areas(plan_b, self._area_hi_by_key)
                     if plan_b is not None else None)
            row = plan_cost_row(plan_b, self._macs_per_layer,
                                layer_areas=areas)
            self._cost_rows[pid] = row
        return row

    # ------------------------------------------------------------------ step
    def step_once(self, now: float | None = None) -> bool:
        """Admit what fits, then run one decode step over the pool.
        Returns ``False`` (and runs nothing) when no slot is active.

        One ``serve.step`` span (attributes ``step``, ``rows`` and
        ``prefill_rows``) holds a span per phase, in order:
        ``serve.step.admit``, ``.inputs`` (page tables and the host
        arrays' transfers), ``.launch`` (the jitted call), ``.wait``
        (``block_until_ready``), ``.sample`` (drift and the argmax's copy
        to the host) and ``.book`` (everything after)."""
        assert self._started, "call start() before step_once()"
        now = time.perf_counter() if now is None else now
        with trace_span("serve.step", step=self._step_idx) as sp:
            preempts_before = self._n_preemptions
            with trace_span("serve.step.admit"):
                self._admit(now)
            occupied = list(self._pool)
            sp.set(rows=len(occupied))
            if not occupied:
                return False

            with trace_span("serve.step.inputs"):
                toks = np.zeros((self.max_slots, 1), np.int32)
                pos = np.zeros(self.max_slots, np.int32)
                active = np.zeros(self.max_slots, bool)
                tables = np.empty((self.max_slots, self.table_entries),
                                  np.int32)
                for i in range(self.max_slots):
                    tables[i] = self._alloc.padded_table(None,
                                                         self.table_entries)
                for idx, seq in occupied:
                    toks[idx, 0] = seq.next_token()
                    pos[idx] = seq.pos
                    active[idx] = True
                    tables[idx] = self._alloc.padded_table(
                        seq.rid, self.table_entries)

                classes = sorted({seq.cls for _, seq in occupied})
                luts, plan_b, glevel, step_level = self._resolve_stack(
                    classes)
                if self._adaptive and luts is None:
                    luts, plan_b = self._luts, self._plan

                jt = (jnp.asarray(toks), jnp.asarray(pos),
                      jnp.asarray(active), jnp.asarray(tables))
            want_shadow = (self._adaptive
                           and (self._controller is not None
                                or self._scheduler is not None)
                           and self._step_idx % self._shadow_every == 0)
            shadow_logits = None
            if want_shadow:
                with trace_span("serve.shadow"):
                    shadow_caches = jax.tree.map(jnp.copy, self._caches)
                    shadow_logits, _ = self._step(shadow_caches, *jt,
                                                  luts=self._exact_luts)
                    shadow_logits.block_until_ready()
            t0 = time.perf_counter()
            with trace_span("serve.step.launch"):
                if self.inject_step_delay:
                    # chaos hook: the sleep sits inside the timed section,
                    # so an injected latency spike is indistinguishable
                    # from a real one to the telemetry, the SLO monitors
                    # and the detectors
                    time.sleep(self.inject_step_delay)
                logits, self._caches = self._step(self._caches, *jt,
                                                  luts=luts)
            with trace_span("serve.step.wait"):
                logits.block_until_ready()
            step_s = time.perf_counter() - t0

            with trace_span("serve.step.sample"):
                drift = None
                if shadow_logits is not None:
                    rows = np.flatnonzero(active)
                    drift = float(jnp.abs(logits[rows]
                                          - shadow_logits[rows]).mean())
                sampled = np.asarray(jnp.argmax(logits, axis=-1), np.int64)
                t_done = time.perf_counter()
            with trace_span("serve.step.book"):
                prefill_rows = self._book(
                    occupied, sampled, t_done, step_s, drift, plan_b, glevel,
                    step_level, preempts_before)
            sp.set(prefill_rows=prefill_rows)
        return True

    def _book(self, occupied, sampled, t_done, step_s, drift, plan_b,
              glevel, step_level, preempts_before) -> int:
        """Everything after a step's sample: advance each slot, evict and
        complete finished requests, then telemetry, health, provenance,
        costs and the control plane.  Returns how many rows fed a prompt
        token (prefill)."""
        by_class: dict[str, dict] = {}
        for idx, seq in occupied:
            row = by_class.setdefault(
                seq.cls, {"rows": 0, "decode_tokens": 0,
                          "prefill_tokens": 0})
            row["rows"] += 1
            generated, first = seq.advance(int(sampled[idx]))
            if generated:
                row["decode_tokens"] += 1
                if self._provenance is not None:
                    self._prov_extend(seq, len(seq.generated) - 1,
                                      plan_b if self._adaptive else None,
                                      step_level)
                    if drift is not None:
                        self._prov_open[seq.rid]["drift"].append(
                            round(drift, 6))
            else:
                row["prefill_tokens"] += 1
            if first:
                seq.first_token_t = t_done
                self.telemetry.record_ttft(
                    seq.cls if self._scheduler is not None else None,
                    t_done - seq.submitted_t)
                self._req_event(
                    "req.decode", rid=seq.rid, cls=seq.cls,
                    ttft_ms=round(1e3 * (t_done - seq.submitted_t), 3),
                    prefill_ms=round(
                        1e3 * max(0.0, (t_done - seq.admitted_t)
                                  - seq.suspended_before_first_s), 3)
                    if seq.admitted_t is not None else None)
            if seq.done:
                self._pool.evict(idx)
                self._alloc.free(seq.rid)
                gen = np.asarray(seq.generated, np.int32)
                self.completions[seq.rid] = gen
                self.last_tokens = gen[None, :]
                self.telemetry.record_request_done(
                    seq.cls if self._scheduler is not None else None)
                b = seq.breakdown(t_done)
                self._req_event("req.done", rid=seq.rid, cls=seq.cls,
                                steps=seq.pos, preempts=seq.preempted,
                                resumes=seq.preempted, **b)
                if self._provenance is not None:
                    self._prov_close(seq.rid)
                    self._provenance.record_done(
                        rid=seq.rid, cls=seq.cls, gen_len=len(gen),
                        steps=seq.pos, preempts=seq.preempted,
                        replica=self.replica_name or None)

        if self._macs_per_layer is not None:
            cost_row = self._cost_row(plan_b if self._adaptive else None)
            for cls, r in by_class.items():
                if r["decode_tokens"]:
                    self.telemetry.record_costs(
                        cls if self._scheduler is not None else None,
                        r["decode_tokens"], cost_row)

        backlog = self._queues.depth
        occ = self._pool.occupancy
        prefill_tokens = sum(r["prefill_tokens"] for r in by_class.values())
        self.telemetry.record_step(
            step=self._step_idx, tick=self._tick, step_s=step_s,
            by_class=by_class,
            decode_tokens=sum(r["decode_tokens"] for r in by_class.values()),
            prefill_tokens=prefill_tokens,
            plan_id=plan_b.plan_id if self._adaptive else None,
            drift=drift, backlog=backlog, occupancy=occ)
        self.telemetry.record_pages(used=self._alloc.used_pages,
                                    total=self._alloc.n_pages)
        if self._health is not None:
            self._health.observe_step(
                step=self._step_idx, step_ms=1e3 * step_s,
                classes=by_class, drift=drift, backlog=backlog,
                occupancy=occ,
                preemptions=self._n_preemptions - preempts_before,
                plan_id=plan_b.plan_id if self._adaptive else None,
                level=glevel,
                pages={"used": self._alloc.used_pages,
                       "free": self._alloc.free_pages,
                       "total": self._alloc.n_pages},
                class_state=(self._scheduler.snapshot(glevel)
                             if self._scheduler is not None else None))

        scheduler = self._scheduler
        if drift is not None and self._adaptive:
            if scheduler is not None:
                for cls in {seq.cls for _, seq in self._pool}:
                    scheduler.observe(cls, drift)
            if self._online is not None and plan_b is not None:
                self._online.update(self._plan_maes(plan_b), drift)
        self._control_plane(
            self._step_idx, "step",
            # occupancy replaces the fixed loop's whole-queue heuristic:
            # requests already in slots are being served, only true
            # admission-queue depth counts as waiting work
            effective_load_ms(1e3 * step_s, backlog=backlog,
                              capacity=self.max_slots, occupancy=occ),
            lambda: (drift if scheduler is None
                     or (glevel is not None
                         and plan_b is scheduler.ladder.plan(glevel))
                     else None),
            glevel)
        self._step_idx += 1
        return prefill_tokens

    # ----------------------------------------------------------------- serve
    def serve(self, profile: LoadProfile, *, controller=None, watcher=None,
              scheduler=None, online=None,
              telemetry: Telemetry | None = None, seed: int = 0,
              steps_per_tick: int | None = None,
              on_step_end: Callable[["ContinuousServingEngine", int],
                                    None] | None = None,
              log: Callable[[str], None] | None = None,
              health=None) -> Telemetry:
        """Serve a synthetic load profile continuously: each tick's
        arrivals join the admission queues, then up to ``steps_per_tick``
        decode steps run before the next tick's arrivals — requests keep
        joining/leaving the pool mid-generation.  After the last tick the
        pool drains to empty."""
        assert profile.prompt_len <= self.prompt_len, (
            f"profile prompts up to {profile.prompt_len} exceed engine "
            f"prompt_len {self.prompt_len}")
        assert profile.gen_len == self.gen_len
        telemetry = self.start(telemetry=telemetry, controller=controller,
                               watcher=watcher, scheduler=scheduler,
                               online=online, health=health, log=log)
        steps = steps_per_tick or self.steps_per_tick
        per_tick = synth_requests(profile, self.cfg.vocab_size, seed)
        try:
            with trace_span("serve.continuous", slots=self.max_slots,
                            pages=self.n_pages):
                for tick in range(profile.n_ticks):
                    self._tick = tick
                    now = time.perf_counter()
                    for r in per_tick[tick]:
                        self.submit(r, now)
                    for _ in range(steps):
                        if not self.step_once():
                            break
                        if on_step_end is not None:
                            on_step_end(self, self._step_idx - 1)
                while self.step_once():
                    if on_step_end is not None:
                        on_step_end(self, self._step_idx - 1)
        except BaseException as e:
            # the flight recorder's crash path: freeze the ring before the
            # exception unwinds past the serve loop, then re-raise
            if self._health is not None:
                self._health.record_crash(e)
            raise
        return telemetry
