"""Beyond-paper: tensorized population search for the SHARED template.

The paper drives a *sequential* SMT solver through a proxy-ordered grid.
This module re-expresses the same exploration as a data-parallel tensor
program (DESIGN.md §4): a population of candidate parameter assignments is
scored against the *entire* input space in one fused evaluation
(:func:`repro.kernels.ops.template_eval` — VPU boolean algebra over
bit-packed truth tables), then evolved with elitist mutation.  On a TPU
mesh the population axis shards over ``data`` — the search scales to
thousands of chips with zero coordination beyond one all-gather of elites
per generation.

Fitness mirrors the paper's proxy logic: sound candidates are ranked by an
(area-proxy) score built from PIT / ITS / literal counts; unsound ones by
their ET violation.  Final winners are *re-verified exhaustively* and
synthesized for true area.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops
from .circuits import Circuit, input_truth_tables
from .engine import SearchOutcome, harvest
from .templates import IGNORE, SharedTemplate, TemplateParams

__all__ = ["population_scorer", "tensor_search"]


def _proxy_score(lits: jax.Array, sel: jax.Array) -> jax.Array:
    """Differentiable-in-spirit area proxy per candidate.

    ``PIT``-weighted + literal count + sum fan-in: the quantities the paper
    shows correlate with synthesized area (§III / Fig. 4).
    """
    used_prod = (sel > 0).any(axis=1)                      # (P, T)
    lit_cnt = ((lits != IGNORE) & used_prod[:, :, None]).sum((1, 2))
    pit = used_prod.sum(axis=1)
    its = (sel > 0).sum(axis=2).max(axis=1)
    return 10.0 * pit + 2.0 * lit_cnt + 3.0 * its


def population_scorer(in_tt: jax.Array, exact_vals: jax.Array,
                      mesh: jax.sharding.Mesh | None = None):
    """``(lits, sel) -> (wce, esum)`` for a candidate population.

    With a ``mesh`` the population axis is split over its ``data`` axis
    and each device scores its own shard: the compiler cannot partition a
    kernel call, and left to itself would gather the whole population
    onto every device.
    """
    def evaluate(lits, sel):
        return ops.template_eval(lits, sel, in_tt, exact_vals)

    if mesh is None:
        return evaluate
    from jax.sharding import PartitionSpec

    # check_vma=False: a kernel call's outputs carry no varying-axes type
    return jax.shard_map(evaluate, mesh=mesh, in_specs=PartitionSpec("data"),
                         out_specs=PartitionSpec("data"), check_vma=False)


def tensor_search(
    exact: Circuit,
    et: int,
    *,
    pit: int | None = None,
    population: int = 4096,
    generations: int = 60,
    elites: int = 64,
    seed: int = 0,
    keep: int = 16,
    seeds: list[TemplateParams] | None = None,
    wall_budget_s: float | None = None,
    mesh: jax.sharding.Mesh | None = None,
) -> SearchOutcome:
    """Evolve shared-template parameters toward minimal-area sound circuits.

    ``seeds``: optional known-good parameter assignments (e.g. from a loose
    SMT query) injected into the initial population — the hybrid
    SMT-feasible / tensor-minimize mode (DESIGN.md §4).

    ``mesh``: optional jax mesh with a ``data`` axis (e.g.
    :func:`repro.launch.mesh.make_fleet_mesh`).  The population axis is
    sharded over it, so one fleet worker drives every local device; the
    per-generation elite argsort is the only cross-shard collective.
    """
    n, m = exact.n_inputs, exact.n_outputs
    T = pit if pit is not None else 2 * m
    tpl = SharedTemplate(n, m, pit=T)
    in_tt = jnp.asarray(input_truth_tables(n))
    exact_vals = jnp.asarray(exact.eval_words().astype(np.int32))
    evaluate = population_scorer(in_tt, exact_vals, mesh)
    pop_sharding = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec

        n_shards = mesh.shape["data"]
        # population must tile evenly over the data axis; round up
        population += (-population) % n_shards
        pop_sharding = NamedSharding(mesh, PartitionSpec("data"))
    key = jax.random.PRNGKey(seed)
    t0 = time.time()

    BIG = jnp.float32(1e6)

    @jax.jit
    def fitness(lits, sel):
        wce, esum = evaluate(lits, sel)
        sound = wce <= et
        score = _proxy_score(lits, sel)
        # unsound candidates are ranked by violation magnitude: the total
        # error gives a smooth descent direction the worst-case alone lacks
        violation = BIG + 100.0 * wce.astype(jnp.float32) + esum.astype(jnp.float32)
        return jnp.where(sound, score, violation), wce

    @jax.jit
    def step(key, lits, sel):
        fit, _ = fitness(lits, sel)
        order = jnp.argsort(fit)
        elite_lits = lits[order[:elites]]
        elite_sel = sel[order[:elites]]
        # children: mutate random elites
        k1, k2, k3, k4, k5 = jax.random.split(key, 5)
        parent = jax.random.randint(k1, (population - elites,), 0, elites)
        c_lits = elite_lits[parent]
        c_sel = elite_sel[parent]
        mut_l = jax.random.bernoulli(k2, 0.04, c_lits.shape)
        new_l = jax.random.randint(k3, c_lits.shape, 0, 3)
        c_lits = jnp.where(mut_l, new_l, c_lits)
        mut_s = jax.random.bernoulli(k4, 0.04, c_sel.shape)
        c_sel = jnp.where(mut_s, 1 - c_sel, c_sel)
        lits = jnp.concatenate([elite_lits, c_lits])
        sel = jnp.concatenate([elite_sel, c_sel])
        if pop_sharding is not None:  # keep the population sharded over data
            lits = jax.lax.with_sharding_constraint(lits, pop_sharding)
            sel = jax.lax.with_sharding_constraint(sel, pop_sharding)
        return k5, lits, sel

    # init population: IGNORE-biased literals (small products are the useful
    # building blocks) and sparse selection (low starting proxies)
    k0, k1, key = jax.random.split(key, 3)
    u = jax.random.uniform(k0, (population, T, n))
    lits = jnp.where(u < 0.25, 0, jnp.where(u < 0.5, 1, 2))  # USE/NEG/IGNORE
    sel = (jax.random.uniform(k1, (population, m, T)) < 0.3).astype(jnp.int32)
    if seeds:
        # tile each seed over a slab of the population (mutation diversifies)
        slab = max(1, population // (4 * len(seeds)))
        row = 0
        for sp in seeds:
            s_lits = np.full((T, n), IGNORE, dtype=np.int32)
            s_sel = np.zeros((m, T), dtype=np.int32)
            t_src = min(sp.lits.shape[0], T)
            s_lits[:t_src] = sp.lits[:t_src]
            s_sel[:, :t_src] = sp.sel[:, :t_src]
            end = min(population, row + slab)
            lits = lits.at[row:end].set(jnp.asarray(s_lits)[None])
            sel = sel.at[row:end].set(jnp.asarray(s_sel)[None])
            row = end
    if pop_sharding is not None:
        lits = jax.device_put(lits, pop_sharding)
        sel = jax.device_put(sel, pop_sharding)

    outcome = SearchOutcome(engine="tensor", benchmark=exact.name, et=et,
                            stats={"generations": 0, "evaluations": 0})
    for g in range(generations):
        if wall_budget_s is not None and time.time() - t0 > wall_budget_s:
            break
        key, lits, sel = step(key, lits, sel)
        outcome.stats["generations"] += 1
        outcome.stats["evaluations"] += population

    # harvest: exhaustively re-verify + synthesize the distinct elites.
    # harvest() raises a descriptive UnsoundResultError if the synthesized
    # netlist disagrees with the template-eval fitness (a kernel bug) —
    # fleet workers report the failing job instead of dying on an assert.
    fit, wce = fitness(lits, sel)
    order = np.asarray(jnp.argsort(fit))
    exact_np = exact.eval_words()
    seen: set[bytes] = set()
    for idx in order:
        if len(outcome.results) >= keep or float(fit[idx]) >= float(BIG):
            break
        p = TemplateParams(
            np.asarray(lits[idx], dtype=np.int8), np.asarray(sel[idx]).astype(bool)
        )
        fingerprint = p.lits.tobytes() + p.sel.tobytes()
        if fingerprint in seen:
            continue
        seen.add(fingerprint)
        outcome.results.append(
            harvest(tpl, p, exact_np, et, engine="tensor",
                    name=f"{exact.name}_tensor", wall_s=time.time() - t0,
                    meta={"fitness": float(fit[idx])})
        )
    outcome.wall_s = time.time() - t0
    return outcome
