"""Cell kind ``rollout_family_probe``: ``rollout_family`` unchanged, and after
it a probe of the slots' recurrent state against the reference's.

The output check of ``rollout_family`` compares logprobs, and a recurrent
state held a precision too low moves them less than the limit that
separates the check's control (PERF.md section 4: with the delta-rule state
in bfloat16 the logprob mean stays under ``check.limit``). So, as
``rollout_hybrid`` does for the state-space state, the state itself is
compared too. ``run`` calls ``rollout_family.run(ctx)`` as it is, whose
numbers are the cell's; then, outside every timed span and with that engine
gone, it builds the engine again from the seed, holds one wave of
``check.state_probe.requests`` requests of ``prompt_len`` + ``new_tokens``
tokens on it, stops it, and holds the state of the slots that changed, in
the FIRST recurrent layer, to the reference's token-by-token state of the
same tokens (``<reference>.first_layer_state``): there the recurrence's
inputs are one bfloat16 matmul away from the reference's, and further down
the error the hidden state has gathered hides what the state's own precision
adds. ``correct`` is both checks. The cell names the state in its family
block: ``"state": {"leaf": the cache's leaf, "dtype_key": the configuration
key of its type, "control_dtype": what the control holds it in}``.

A head's error is norm(slot's state - reference's) / norm(reference's);
``state_rel`` is the mean over the probe's requests of the worst head's, the
head that remembers longest and so gathers the most rounding of its state.
"""

from __future__ import annotations

import copy
import gc

import numpy as np

from benchlib import harness, loadgen, traffic
from benchlib.cells import rollout_family


def state_marks(eng, leaf: str) -> np.ndarray:
    """One number a slot: the sum of |state| over every recurrent layer. Read
    while the engine is idle or stopped (a running loop donates the array)."""
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda s: jnp.sum(jnp.abs(s.astype(jnp.float32)), axis=tuple(i for i in range(s.ndim) if i != 1)))
    return np.asarray(fn(eng.cache[leaf]))


def first_layer_states(eng, leaf: str, slots) -> np.ndarray:
    """The first recurrent layer's state of ``slots`` as float32 [slots,
    heads, ...], in the reference's order of axes (the program says how its
    cache lays a slot's state out: ``slot_state_view``)."""
    import jax.numpy as jnp

    rows = eng.cache[leaf][0][jnp.asarray(slots, jnp.int32)].astype(jnp.float32)
    return np.asarray(eng.model.slot_state_view(eng.model_cfg, leaf, rows))


def probe(cfg: dict, p: dict, mix: dict, seed: int, devs, control: bool) -> dict:
    """Build the engine from the seed, run the probe's wave on it, and
    compare the changed slots' state with the reference's."""
    fam, sp, chk = p["family"], p["server"], p["check"]
    pp, st = chk["state_probe"], fam["state"]
    if control:  # the step below: the state held in the control's type
        cfg = copy.deepcopy(cfg)
        cfg.setdefault("assumed", {})[st["dtype_key"]] = st["control_dtype"]
    eng, server, make_params = rollout_family.build_server(cfg, sp, fam, p["dtype"], seed, devs, control)
    server.start()
    try:
        rng = traffic.rng_for(seed, 11)
        reqs = [
            (rng.integers(0, int(cfg["vocab_size"]), int(pp["prompt_len"])).tolist(), int(pp["new_tokens"]))
            for _ in range(int(pp["requests"]))
        ]
        before = state_marks(eng, st["leaf"])
        recs = loadgen.held_wave(server.address, reqs, mix["sampling"])
    finally:
        server.stop()
    changed = np.flatnonzero(state_marks(eng, st["leaf"]) != before)
    states = first_layer_states(eng, st["leaf"], changed[: 2 * len(reqs)]).astype(np.float64)
    del eng, server
    gc.collect()
    ok = [r for r in recs if r.get("ok")]
    out = {"probe_requests": len(recs), "probe_failed": len(recs) - len(ok), "slots_changed": int(len(changed)), "state_rel": None}
    if len(ok) < len(recs) or len(changed) != len(recs):
        return out
    reference = rollout_family.piece(fam, "reference")
    params = make_params()
    per_head = []
    for (prompt, _), r in zip(reqs, recs):
        consumed = prompt + r["tokens"][:-1]  # the last sampled token was fed to nothing
        ref = reference.first_layer_state(params, cfg, consumed, pad_to=int(sp["max_seq_len"])).astype(np.float64)
        axes = tuple(range(2, states.ndim))
        err = ((states - ref) ** 2).sum(axis=axes)  # [slots, heads]; a request's slot is the changed one nearest its reference
        per_head.append(np.sqrt(err[int(err.sum(axis=1).argmin())] / (ref * ref).sum(axis=tuple(range(1, ref.ndim)))))
    e = np.stack(per_head)  # [requests, heads]
    out.update(
        state_rel=float(e.max(axis=1).mean()),
        state_rel_head_mean=float(e.mean()),
        state_rel_head_p90=float(np.percentile(e, 90, axis=1).mean()),
        state_tokens=int(sum(len(q) + len(r["tokens"]) - 1 for (q, _), r in zip(reqs, recs))),
    )
    return out


def run(ctx: dict) -> dict:
    out = rollout_family.run(ctx)
    # what the cell's counters read over the window: a state rebuilt by prefill there is a preemption (none may occur)
    seen = (out["facts"].get("extra") or {}).get("window_counters") or {}
    harness.log("inside the window: " + ", ".join(f"{k} +{v:.0f}" for k, v in seen.items()))
    cell, reh = ctx["cell"], ctx["rehearsal"]
    p = dict(cell["params"])
    cfg, mix = dict(cell["model"]), cell["traffic"]
    if reh:
        cfg, mix = reh["model"], reh.get("traffic", mix)
        p = {**p, **reh["params"]}
    devs = harness.devices_for(cell["chips"], bool(reh))
    res = probe(cfg, p, mix, ctx["seed"], devs, ctx["control"])
    limit = float(p["check"]["limit_state_rel"])
    good = res["state_rel"] is not None and res["state_rel"] <= limit
    harness.log(
        f"state check: {res['probe_requests']} requests ({res['probe_failed']} failed, the state of {res['slots_changed']} slots "
        f"changed), {res.get('state_tokens', 0)} tokens consumed, first recurrent layer: worst head's |slot's state - reference| / "
        f"|reference|, mean over the requests = {res['state_rel']} (limit {limit}); the heads' mean "
        f"{res.get('state_rel_head_mean')}, their 90th percentile {res.get('state_rel_head_p90')}"
        + (f" [CONTROL: state in {p['family']['state']['control_dtype']}]" if ctx["control"] else "")
    )
    out["facts"]["check"].update(res)
    out["correct"] = bool(out["correct"] and good)
    return out
