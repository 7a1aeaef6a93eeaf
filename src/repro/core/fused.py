"""Fused on-device fixpoint rounds: one compiled ``lax.while_loop`` per pass.

The host round loop in :meth:`repro.core.engine_jax.JaxEngine._forward` (and
the overdelete wave loop of :mod:`repro.core.incremental_spmd`) dispatches
one process step, one plan evaluation per delta plan and one squeeze PER
ROUND, with a device->host round trip between rounds to read the convergence
and overflow flags.  At steady state that dispatch count — not sort
bandwidth — is the per-event floor (ROADMAP "kill the dispatch floor";
BENCH_incremental.json records it as ``dispatches_per_event``).

This module moves the whole inner loop into a single compiled fixpoint:

* :func:`fused_forward_rounds` — the forward round loop (process ->
  delta-plan evaluation -> squeeze) as one ``lax.while_loop`` whose carry
  holds the arena columns, the candidate stream and sticky overflow/exit
  flags.  Convergence is decided on device; capacity overflow, contradiction
  and rho-reaches-a-rule-constant are checked ONCE on exit, not per round.
* :func:`fused_delete_waves` — the DRed overdelete wave loop (tombstone
  plans -> :func:`~repro.core.incremental_spmd._od_step`) fused the same
  way.

Every delta and tombstone plan starts at its delta atom and chains the rest
bound-first (:func:`~repro.core.engine_jax.build_plans`), so each join step
range-scans one of the arena's two persistent indexes, in (s, p, o) or
(p, o, s) order, where its fixed positions prefix one: an iteration's joins
then scale with the delta, not with the arena.  :func:`fused_forward_rounds`
carries both indexes and its process step maintains them;
:func:`fused_delete_waves` reads them as loop constants.

Host-only decisions stay host decisions, but move from per-round to
per-exit:

* **Capacity retry** — every overflow flag is a sticky carry bool; the loop
  exits on the first raised flag and the host raises the usual
  :class:`~repro.core.engine_jax.CapacityError`, whose snapshot rollback
  makes the (garbage) post-overflow carry state irrelevant.
* **Rule rewriting** — rules are rewritten on the host when rho reaches a
  rule *constant*.  The invariant at entry is that every constant is a rho
  fixed point (the program is always rewritten under a compressed rho), so
  the device detects the exit condition exactly as
  ``any(rep[c] != c for rule constants c)`` against the post-merge rep.
  The exit iteration's plan evaluation is *nullified* by evaluating at an
  impossible round (every epoch predicate matches nothing — see
  ``_epoch_ok``), and the host re-evaluates that round's plans with the
  rewritten constants before resuming — so plans run exactly once per
  round, with the same constants the host loop would have used.

Both fns register with the trace audit (``fforward`` / ``fwave``): the
while_loop body must lint clean under NoArenaSort / NoArenaScatter (fwave
carries the od step's deliberate exemption) / DtypeSafety.
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from .engine_jax import (
    I32,
    _pow2,
    _squeeze_stream,
    build_plans,
    eval_plan,
    process_candidates,
    register_auditable,
)
from .terms import is_var

__all__ = [
    "forward_plan_signature",
    "fused_delete_waves",
    "fused_forward_rounds",
    "program_tables",
]


def forward_plan_signature(program, tombstone: bool = False) -> tuple:
    """Static plan signature of a program: one ``(rule_idx, plan,
    head_var_slots)`` entry per delta (or tombstone) plan — the static half
    the fused fns close over (the traced half is :func:`program_tables`)."""
    sig = []
    for k, rule in enumerate(program.rules):
        head_slots = tuple(t if is_var(t) else None for t in rule.head)
        for plan in build_plans(rule, full=False, tombstone=tombstone):
            sig.append((k, tuple(plan), head_slots))
    return tuple(sig)


def program_tables(program):
    """Traced constant tables of a program.

    Returns ``(atom_consts, head_consts, const_vals, const_valid)``:

    * ``atom_consts`` (n_rules, max_atoms, 3) / ``head_consts`` (n_rules, 3)
      int32 — the per-rule constant arrays :func:`eval_plan` takes (variable
      positions hold garbage 0, exactly like the host driver builds them);
    * ``const_vals`` / ``const_valid`` — the deduplicated set of every rule
      constant, padded to a power of two.  Rule rewriting is a host
      decision; the device only needs to detect *when* it is due, and the
      rule-constant invariant (every constant is a rho fixed point at
      operation entry) makes that exactly
      ``any(const_valid & (rep[const_vals] != const_vals))``.

    Constants are traced arguments (as everywhere in the engine) so a host
    rewrite never re-traces the fused fn.
    """
    rules = program.rules
    n_rules = max(len(rules), 1)
    max_atoms = max((len(r.body) for r in rules), default=1)
    ac = np.zeros((n_rules, max(max_atoms, 1), 3), np.int32)
    hc = np.zeros((n_rules, 3), np.int32)
    consts: set[int] = set()
    for k, rule in enumerate(rules):
        for j, atom in enumerate(rule.body):
            for pos, t in enumerate(atom):
                if not is_var(t):
                    ac[k, j, pos] = t
                    consts.add(int(t))
        for pos, t in enumerate(rule.head):
            if not is_var(t):
                hc[k, pos] = t
                consts.add(int(t))
    cs = np.asarray(sorted(consts), np.int32)
    width = _pow2(max(cs.shape[0], 1))
    vals = np.zeros((width,), np.int32)
    vals[: cs.shape[0]] = cs
    valid = np.arange(width) < cs.shape[0]
    return (
        jnp.asarray(ac), jnp.asarray(hc),
        jnp.asarray(vals), jnp.asarray(valid),
    )


# round sentinel for the nullified exit iteration: far below any real round,
# so every epoch/tombstone predicate of ``_epoch_ok`` matches zero rows and
# the iteration's plan evaluation contributes exactly nothing (collectives
# still run — a ``cond`` around them would diverge across shards)
_NULL_ROUND = -(1 << 20)


def _pany(x, axis):
    x = jnp.any(x)
    if axis is None:
        return x
    return jax.lax.psum(x.astype(I32), axis) > 0


def _eval_plans(
    spo, epoch, marked, tomb, sorted_keys, sort_perm, pos_keys, pos_perm,
    r_eval, atom_consts, head_consts, plans, width,
    *, bind_cap, plan_out_cap, axis, use_kernel,
):
    """Evaluate the static ``plans`` and squeeze/pad the bucketed heads to
    ``width`` rows.  The fused analogue of the host loop's per-round
    ``_eval_rule`` + ``_bucket_cands`` + squeeze — one traced block instead
    of one dispatch per plan.  Returns
    ``(heads, valid, n_deriv, n_appl, ov_bind, ov_out, ov_squeeze)``
    (scalars local to the shard; callers psum)."""
    outs, vals = [], []
    n_deriv = jnp.zeros((), I32)
    n_appl = jnp.zeros((), I32)
    ov_bind = jnp.zeros((), bool)
    ov_out = jnp.zeros((), bool)
    ov_squeeze = jnp.zeros((), bool)
    for k, plan_t, head_slots in plans:
        o, v, nd, na, ovb, ovo = eval_plan(
            spo, epoch, marked, tomb, sorted_keys, sort_perm,
            pos_keys, pos_perm, r_eval,
            atom_consts[k], head_consts[k],
            plan=plan_t, head_var_slots=head_slots,
            bind_cap=bind_cap, out_cap=plan_out_cap, axis=axis,
            use_kernel=use_kernel,
        )
        outs.append(o)
        vals.append(v)
        n_deriv = n_deriv + nd.reshape(())
        n_appl = n_appl + na.reshape(())
        ov_bind = ov_bind | jnp.any(ovb)
        ov_out = ov_out | jnp.any(ovo)
    if not outs:
        heads = jnp.zeros((width, 3), I32)
        valid = jnp.zeros((width,), bool)
    else:
        heads = jnp.concatenate(outs, axis=0)
        valid = jnp.concatenate(vals, axis=0)
        if heads.shape[0] > width:
            heads, valid, sq = _squeeze_stream(heads, valid, target=width)
            ov_squeeze = jnp.any(sq)
        elif heads.shape[0] < width:
            pad = width - heads.shape[0]
            heads = jnp.concatenate([heads, jnp.zeros((pad, 3), I32)])
            valid = jnp.concatenate([valid, jnp.zeros((pad,), bool)])
    return heads, valid, n_deriv, n_appl, ov_bind, ov_out, ov_squeeze


def fused_forward_rounds(
    spo, epoch, marked, tomb, n_used, rep, sort_perm, sorted_keys,
    pos_perm, pos_keys, cands, cand_valid, r0, max_inner,
    atom_consts, head_consts, const_vals, const_valid,
    *,
    plans: tuple,
    rewrite_cap: int,
    bind_cap: int,
    plan_out_cap: int,
    pair_cap: int,
    route_cap: int | None,
    axis: str | None,
    n_shards: int,
    use_kernel: bool,
):
    """The forward round loop as ONE compiled fixpoint.

    Per iteration (identical to one host round): process the candidate
    stream at round ``r`` (normalise, merge rho, sweep, insert), then
    evaluate every delta plan at ``r + 1`` and squeeze the bucketed heads
    back to the carry's stream width.  The loop exits when

    * the stream empties (convergence — the only healthy exit),
    * any capacity flag fires (host raises the matching CapacityError and
      rolls back, so the post-overflow carry is never observed),
    * a contradiction is derived,
    * rho reaches a rule constant (``consts_changed``) — the exit
      iteration's plan evaluation is nullified (``_NULL_ROUND``) and the
      host re-runs it with the rewritten constants, or
    * ``max_inner`` iterations ran (host raises "did not converge").

    Both persistent indexes ride in the carry: the process step maintains
    them and the plans' joins range-scan them.

    Returns ``(spo, epoch, marked, n_used, rep, sort_perm, sorted_keys,
    pos_perm, pos_keys, cands, cand_valid, flags)`` with ``flags`` the exit
    report (iteration
    count, sticky overflow bits, the exit round's ``n_new``, and the
    accumulated stats deltas).
    """
    width = cands.shape[0]
    assert width == plan_out_cap, (width, plan_out_cap)
    n_res = rep.shape[0]
    false = jnp.zeros((), bool)

    carry = {
        "r": jnp.asarray(r0, I32).reshape(()),
        "iters": jnp.zeros((), I32),
        "spo": spo, "epoch": epoch, "marked": marked, "n_used": n_used,
        "rep": rep, "sort_perm": sort_perm, "sorted_keys": sorted_keys,
        "pos_perm": pos_perm, "pos_keys": pos_keys,
        "cands": cands, "cand_valid": cand_valid,
        "have_cands": jnp.ones((), bool),
        "n_new": jnp.zeros((), I32),
        "n_pairs": jnp.zeros((), I32),
        "n_reflexive": jnp.zeros((1,), I32),
        "n_deriv": jnp.zeros((1,), I32),
        "n_appl": jnp.zeros((1,), I32),
        "ov_store": false, "ov_rewrite": false, "ov_route": false,
        "ov_pair": false, "ov_bind": false, "ov_out": false,
        "ov_squeeze": false,
        "contradiction": false, "consts_changed": false,
    }

    def _stop(c):
        return (
            c["ov_store"] | c["ov_rewrite"] | c["ov_route"] | c["ov_pair"]
            | c["ov_bind"] | c["ov_out"] | c["ov_squeeze"]
            | c["contradiction"] | c["consts_changed"]
        )

    def cond(c):
        # the first iteration always runs (the host loop's ``first`` flag:
        # a padded-empty seed stream still needs its convergence round)
        return (c["iters"] == 0) | (
            c["have_cands"] & ~_stop(c) & (c["iters"] < max_inner)
        )

    def body(c):
        r = c["r"] + 1
        (spo_, epoch_, marked_, n_used_, rep_, perm_, keys_, pperm_, pkeys_,
         fl) = (
            process_candidates(
                c["spo"], c["epoch"], c["marked"], c["n_used"], c["rep"],
                c["sort_perm"], c["sorted_keys"], c["pos_perm"], c["pos_keys"],
                c["cands"], c["cand_valid"], r, rewrite_cap=rewrite_cap, axis=axis, n_shards=n_shards,
                route_cap=route_cap, pair_cap=pair_cap,
                use_kernel=use_kernel,
            )
        )
        ov_store = _pany(fl["ov_store"], axis)
        ov_rewrite = _pany(fl["ov_rewrite"], axis)
        ov_route = _pany(fl["ov_route"], axis)
        ov_pair = _pany(fl["ov_pair"], axis)
        contradiction = jnp.any(fl["contradiction"])  # already global
        consts_changed = jnp.any(
            const_valid
            & (rep_[jnp.clip(const_vals, 0, n_res - 1)] != const_vals)
        )
        stop = (
            ov_store | ov_rewrite | ov_route | ov_pair
            | contradiction | consts_changed
        )
        n_new = fl["n_new"].reshape(())
        if axis is not None:
            n_new = jax.lax.psum(n_new, axis)

        # plan evaluation for the fresh delta at r + 1; nullified when this
        # iteration is the exit (stats and outputs then contribute zero and
        # the host re-evaluates the round after handling the exit cause)
        r_eval = jnp.where(stop, jnp.asarray(_NULL_ROUND, I32), r + 1)
        heads, valid, n_deriv, n_appl, ov_bind, ov_out, ov_squeeze = (
            _eval_plans(
                spo_, epoch_, marked_, tomb, keys_, perm_, pkeys_, pperm_,
                r_eval,
                atom_consts, head_consts, plans, width,
                bind_cap=bind_cap, plan_out_cap=plan_out_cap, axis=axis,
                use_kernel=use_kernel,
            )
        )
        return {
            "r": r, "iters": c["iters"] + 1,
            "spo": spo_, "epoch": epoch_, "marked": marked_,
            "n_used": n_used_, "rep": rep_,
            "sort_perm": perm_, "sorted_keys": keys_,
            "pos_perm": pperm_, "pos_keys": pkeys_,
            "cands": heads, "cand_valid": valid,
            "have_cands": _pany(valid, axis),
            "n_new": n_new,
            "n_pairs": c["n_pairs"] + fl["n_pairs"].reshape(()).astype(I32),
            "n_reflexive": c["n_reflexive"] + fl["n_reflexive"],
            "n_deriv": c["n_deriv"] + n_deriv[None],
            "n_appl": c["n_appl"] + n_appl[None],
            "ov_store": c["ov_store"] | ov_store,
            "ov_rewrite": c["ov_rewrite"] | ov_rewrite,
            "ov_route": c["ov_route"] | ov_route,
            "ov_pair": c["ov_pair"] | ov_pair,
            "ov_bind": c["ov_bind"] | _pany(ov_bind, axis),
            "ov_out": c["ov_out"] | _pany(ov_out, axis),
            "ov_squeeze": c["ov_squeeze"] | _pany(ov_squeeze, axis),
            "contradiction": c["contradiction"] | contradiction,
            "consts_changed": c["consts_changed"] | consts_changed,
        }

    c = jax.lax.while_loop(cond, body, carry)
    flags = {
        k: c[k]
        for k in (
            "iters", "have_cands", "n_new", "n_pairs",
            "n_reflexive", "n_deriv", "n_appl",
            "ov_store", "ov_rewrite", "ov_route", "ov_pair",
            "ov_bind", "ov_out", "ov_squeeze",
            "contradiction", "consts_changed",
        )
    }
    return (
        c["spo"], c["epoch"], c["marked"], c["n_used"], c["rep"],
        c["sort_perm"], c["sorted_keys"], c["pos_perm"], c["pos_keys"],
        c["cands"], c["cand_valid"], flags,
    )


def fused_delete_waves(
    spo, epoch, marked, tomb, sorted_keys, sort_perm, pos_keys, pos_perm,
    rep, sizes, suspect, max_inner, atom_consts, head_consts,
    *,
    plans: tuple,
    bind_cap: int,
    plan_out_cap: int,
    route_cap: int | None,
    refl_cap: int,
    axis: str | None,
    n_shards: int,
    use_kernel: bool,
):
    """The DRed overdelete wave loop as ONE compiled fixpoint.

    Per iteration (identical to one host wave): evaluate every tombstone
    plan at wave ``w`` against the carry's ``tomb`` column, squeeze the
    bucketed heads to the delta width, and run
    :func:`~repro.core.incremental_spmd._od_step` (mask reduction skipped —
    dead-plan elimination is a host optimisation the fused loop does not
    need).  Exits when a wave tags nothing new, any capacity flag fires, or
    ``max_inner`` waves ran.  The arena columns other than ``tomb`` are
    loop constants — tombstone tagging never changes liveness, so both
    persistent indexes stay exact for every wave's probes and joins, and
    the loop maintains neither.

    Returns ``(tomb, suspect, flags)``.
    """
    from .incremental_spmd import _od_step  # deferred: module import cycle

    false = jnp.zeros((), bool)
    carry = {
        "w": jnp.zeros((), I32),
        "iters": jnp.zeros((), I32),
        "tomb": tomb, "suspect": suspect,
        "n_od": jnp.zeros((), I32),
        "n_new": jnp.zeros((), I32),
        "ov_route": false, "ov_refl": false,
        "ov_bind": false, "ov_out": false, "ov_squeeze": false,
    }

    def _stop(c):
        return (
            c["ov_route"] | c["ov_refl"] | c["ov_bind"] | c["ov_out"]
            | c["ov_squeeze"]
        )

    def cond(c):
        return (c["iters"] == 0) | (
            (c["n_new"] > 0) & ~_stop(c) & (c["iters"] < max_inner)
        )

    def body(c):
        w = c["w"] + 1
        heads, hv, _nd, _na, ov_bind, ov_out, ov_squeeze = _eval_plans(
            spo, epoch, marked, c["tomb"], sorted_keys, sort_perm,
            pos_keys, pos_perm, w,
            atom_consts, head_consts, plans, plan_out_cap,
            bind_cap=bind_cap, plan_out_cap=plan_out_cap, axis=axis,
            use_kernel=use_kernel,
        )
        tomb_, suspect_, n_new, ov_route, ov_refl, _masks = _od_step(
            spo, epoch, marked, c["tomb"], sorted_keys, sort_perm, rep,
            sizes, c["suspect"], heads, hv, w,
            axis=axis, n_shards=n_shards, route_cap=route_cap,
            refl_cap=refl_cap, with_masks=False, use_kernel=use_kernel,
        )
        n_new = n_new.reshape(())  # already globally summed by _od_step
        return {
            "w": w, "iters": c["iters"] + 1,
            "tomb": tomb_, "suspect": suspect_,
            "n_od": c["n_od"] + n_new, "n_new": n_new,
            "ov_route": c["ov_route"] | _pany(ov_route, axis),
            "ov_refl": c["ov_refl"] | _pany(ov_refl, axis),
            "ov_bind": c["ov_bind"] | _pany(ov_bind, axis),
            "ov_out": c["ov_out"] | _pany(ov_out, axis),
            "ov_squeeze": c["ov_squeeze"] | _pany(ov_squeeze, axis),
        }

    c = jax.lax.while_loop(cond, body, carry)
    flags = {
        k: c[k]
        for k in (
            "iters", "n_od", "n_new",
            "ov_route", "ov_refl", "ov_bind", "ov_out", "ov_squeeze",
        )
    }
    return c["tomb"], c["suspect"], flags


# -- audit trace builders (repro.analysis) ----------------------------------
#
# The fused fns join the inventory like every other hot compiled fn: traced
# single-device, un-jitted, at the caller's probe geometry.  ``fforward``
# carries no exemptions — the while body's sorts are all stream or bind
# width (the dedup sort, the fresh delta's (p, o, s) sort that feeds the
# second index's merge, the generic join's binding sort), the arena is only
# range-probed through its two indexes, and the scatters are delta width.
# ``fwave`` inlines ``_od_step``, whose
# per-``n_res`` mask reductions scatter arena-length update streams by
# design (the od family's documented exemption).

def _audit_tables(engine, state):
    from .engine_jax import I32 as _I32  # noqa: F401 (symmetry with peers)

    return program_tables(state.program)


@register_auditable("fforward")
def _audit_fforward(engine, state):
    width = engine.delta_out
    ac, hc, cv, cvd = _audit_tables(engine, state)
    fn = partial(
        fused_forward_rounds,
        plans=forward_plan_signature(state.program),
        rewrite_cap=engine.delta_rewrite, bind_cap=engine.delta_bind,
        plan_out_cap=width, pair_cap=engine.pair_cap, route_cap=None,
        axis=None, n_shards=1, use_kernel=engine.use_kernel,
    )
    jx = jax.make_jaxpr(fn)(
        state.spo, state.epoch, state.marked, state.tomb, state.n_used,
        state.rep, state.sort_perm, state.sorted_keys,
        state.pos_perm, state.pos_keys,
        jnp.zeros((width, 3), I32), jnp.zeros((width,), bool),
        jnp.asarray(1, I32), jnp.asarray(64, I32), ac, hc, cv, cvd,
    )
    yield "fforward", jx


@register_auditable("fwave", skip_passes=("NoArenaScatter",))
def _audit_fwave(engine, state):
    width = engine.delta_out
    ac, hc, _cv, _cvd = _audit_tables(engine, state)
    fn = partial(
        fused_delete_waves,
        plans=forward_plan_signature(state.program, tombstone=True),
        bind_cap=engine.delta_bind, plan_out_cap=width, route_cap=None,
        refl_cap=width, axis=None, n_shards=1, use_kernel=engine.use_kernel,
    )
    jx = jax.make_jaxpr(fn)(
        state.spo, state.epoch, state.marked, state.tomb,
        state.sorted_keys, state.sort_perm, state.pos_keys, state.pos_perm,
        state.rep,
        jnp.zeros((state.n_res,), I32), jnp.zeros((state.n_res,), bool),
        jnp.asarray(64, I32), ac, hc,
    )
    yield "fwave", jx
