"""Cell kind ``rollout_family_select``: ``rollout_family`` unchanged, and after
it a probe of a learned index's cached keys and of the selection made from
them, against the reference's.

The output check of ``rollout_family`` compares logprobs. Where a query
attends to the ``index_topk`` cached tokens its layer's index picks, a
program that picked by another rule moves the logprobs only as far as the
seeded attention is peaked, and one whose cached index keys had drifted would
move them less than that: so, as ``rollout_family_probe`` does for a
recurrent state, what the mechanism keeps is compared too. ``run`` calls
``rollout_family.run(ctx)`` as it is, whose numbers are the cell's; then,
outside every timed span and with that engine gone, it builds the engine
again from the seed, admits one wave of ``check.select_probe.requests``
requests of ``prompt_len`` tokens that decode on, holds the engine at its
fence after ``min_new_tokens`` of each, and reads in the FIRST layer (whose
input is the token's embedding alone, so that nothing but the cached keys
enters from the past):

  key_rel          norm(cached index keys - reference's) / norm(reference's)
                   over every cached token of the probe's requests, the
                   mean over the requests;
  selected_common  at ``positions`` places a request, spread from
                   ``index_topk`` to its last cached token, the share of the
                   reference's S_t that the program's own selection routine
                   (``hybrid.first_layer_selection``: the index's launch and
                   ``select_top``, as a decode step runs them, over the
                   slot's cached pages) returns too; the mean over places.

``correct`` is the logprobs AND ``key_rel <= check.limit_key_rel`` AND
``selected_common >= check.limit_selected_common``. Under ``control`` the
logprob check has the family's control (int8-rounded weights), and the
probe puts a selection by ANOTHER RULE in the program's place, the last
``index_topk`` tokens: both must read not correct.
"""

from __future__ import annotations

import gc
import threading
import time

import numpy as np

from benchlib import harness, loadgen, traffic
from benchlib.cells import rollout_family


def _held(eng) -> None:
    eng.pause_generation("hold")
    if not eng.wait_fence_ack(120):
        raise RuntimeError("the engine did not reach its hold fence")


def probe(cfg: dict, p: dict, mix: dict, seed: int, devs, control: bool) -> dict:
    """Build the engine from the seed, hold one wave of long requests on it
    mid-decode, and compare the first layer's cached index keys and the
    selection made from them with the reference's."""
    import jax
    import jax.numpy as jnp

    fam, sp, chk = p["family"], p["server"], p["check"]
    pp = chk["select_probe"]
    topk, psz = int(cfg["index_topk"]), int(sp["page_size"])
    eng, server, make_params = rollout_family.build_server(cfg, sp, fam, p["dtype"], seed, devs, False)
    server.start()
    out = {"probe_requests": int(pp["requests"]), "probe_failed": 0, "key_rel": None, "selected_common": None}
    try:
        rng = traffic.rng_for(seed, 13)
        prompts = [rng.integers(0, int(cfg["vocab_size"]), int(pp["prompt_len"])).tolist() for _ in range(int(pp["requests"]))]
        threads = [
            threading.Thread(target=loadgen.generate, args=(server.address, q, int(pp["new_tokens"]), mix["sampling"]), daemon=True)
            for q in prompts
        ]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 300.0
        want = int(pp["min_new_tokens"])

        def running():
            return [t for t in eng._slot_task if t is not None and len(t.out_tokens) >= want]

        while len(running()) < len(prompts) and time.monotonic() < deadline:
            time.sleep(0.05)
        _held(eng)  # the loop idles at its fence: slots, pages and the cache are ours to read
        held = [(s, t) for s, t in enumerate(eng._slot_task) if t is not None]
        rows = []
        for slot, task in held:
            ids = list(task.req.input_ids) + list(task.out_tokens)
            n = len(ids) - 2  # tokens whose keys are surely cached: the last sampled one was fed to nothing yet
            pages = list(eng._slot_pages[slot])
            at = np.unique(np.linspace(min(topk, n - 1), n - 1, int(pp["positions"])).astype(np.int64))
            rows.append({"ids": ids[:n], "pages": pages, "at": at})
        wp = max(len(r["pages"]) for r in rows) if rows else 1
        wp = -(-wp // 4) * 4  # whole blocks of the index launch
        use_kernel = bool(eng._use_kernel)
        select = jax.jit(
            lambda params, cache, ids, pos, pt: eng.model.first_layer_selection(
                params, eng.model_cfg, ids, pos, cache, pt, page_size=psz, use_kernel=use_kernel
            )
        )
        got = []
        for r in rows:
            pt = np.zeros((len(r["at"]), wp), np.int32)
            pt[:, : len(r["pages"])] = r["pages"]
            ids = np.asarray([r["ids"][t] for t in r["at"]], np.int32)
            with jax.set_mesh(eng.mesh):
                chosen = np.asarray(select(eng.params, eng.cache, jnp.asarray(ids), jnp.asarray(r["at"].astype(np.int32)), jnp.asarray(pt)))
                keys = eng.cache["idx"][0, 0][jnp.asarray(r["pages"], jnp.int32)].astype(jnp.float32)
                keys = np.asarray(eng.model.index_key_view(eng.model_cfg, keys))  # the program says how a page lays a key out
            got.append({"chosen": chosen, "keys": keys.reshape(-1, keys.shape[-1])[: len(r["ids"])]})
        eng.continue_generation()
        loadgen.post(server.address, "/drain", {"budget_s": 0}, timeout=120)
        for t in threads:
            t.join(120)
    finally:
        server.stop()
    del eng, server
    gc.collect()
    if len(rows) < len(prompts):
        out["probe_failed"] = len(prompts) - len(rows)
        return out
    reference = rollout_family.piece(fam, "reference")
    params = make_params()
    key_rel, common, picked = [], [], 0
    for r, g in zip(rows, got):
        ref = reference.first_layer_selection(params, cfg, r["ids"], r["at"], pad_to=int(sp["max_seq_len"]))
        key_rel.append(float(np.linalg.norm(g["keys"] - ref["keys"]) / np.linalg.norm(ref["keys"])))
        for i, t in enumerate(r["at"]):
            want_set = ref["chosen"][int(t)]
            if control:  # another rule in the program's place: the most recent index_topk tokens
                have = np.arange(t + 1) > t - topk
            else:
                have = g["chosen"][i, : t + 1]
            common.append(float((want_set & have).sum() / want_set.sum()))
            picked += int(have.sum())
    out.update(
        key_rel=float(np.mean(key_rel)),
        key_rel_max=float(np.max(key_rel)),
        selected_common=float(np.mean(common)),
        selected_common_min=float(np.min(common)),
        places=len(common),
        picked_a_place=picked / max(1, len(common)),
        cached_tokens=int(sum(len(r["ids"]) for r in rows)),
    )
    return out


def run(ctx: dict) -> dict:
    out = rollout_family.run(ctx)
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
    chk = p["check"]
    lim_key, lim_common = float(chk["limit_key_rel"]), float(chk["limit_selected_common"])
    good = (
        res["key_rel"] is not None
        and res["key_rel"] <= lim_key
        and res["selected_common"] >= lim_common
    )
    harness.log(
        f"selection check: {res['probe_requests']} requests ({res['probe_failed']} not held), {res.get('cached_tokens', 0)} cached tokens, first layer: "
        f"|cached index keys - reference| / |reference| = {res['key_rel']} (limit {lim_key}; worst request {res.get('key_rel_max')}); at "
        f"{res.get('places', 0)} places the share of the reference's S_t the selection returns = {res['selected_common']} (limit {lim_common}; "
        f"worst place {res.get('selected_common_min')}; {res.get('picked_a_place')} tokens picked a place)"
        + (" [CONTROL: the last index_topk tokens in the selection's place]" if ctx["control"] else "")
    )
    out["facts"]["check"].update(res)
    out["correct"] = bool(out["correct"] and good)
    return out
