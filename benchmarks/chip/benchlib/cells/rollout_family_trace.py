"""Cell kind ``rollout_family_trace``: ``rollout_family`` unchanged, and after
it a probe of a block-diffusion model's generation TRACE, against the
reference's.

The output check of ``rollout_family`` hands the reference the token ids
alone, and ``loadgen.generate`` keeps a reply's tokens and logprobs alone. For
a model that generates by diffusion over blocks that checks the timed path's
rule (``sequential`` at a fixed count of passes a block: every pass's state is
a function of the ids) in every block a request generated whole, and is blind
to two things: the confidence rules, whose order depends on candidates that
were thrown away, and the one block a request that holds the prompt's end
(its state depends on the prompt's length mod the block's, which the reference
is not told). So, as ``rollout_family_select`` and ``rollout_family_probe`` do
for what a logprob mean is blind to, ``run`` calls ``rollout_family.run(ctx)``
as it is, whose numbers are the cell's; then, outside every timed span and
with that engine gone, it builds the engine again from the seed, sends one
held wave of ``check.trace_probe.requests`` requests under each rule of
``check.trace_probe.rules`` (the rule and ``denoising_steps`` as request
parameters; prompts of ``prompt_len`` + 0, 1, 2, 3 tokens, ``new_tokens``
each), keeps the replies WHOLE (``loadgen.post``: tokens, logprobs,
``output_denoise_pass``) and holds them to the reference given the prompt's
length and the pass numbers:

  trace_abs     mean |logprob - sdar_reference.trace_logprobs(ids, prompt
                length, output_denoise_pass)| over the wave, a rule at a time
                (``trace_abs_by_rule``) and together;
  trace_shape   whether every block's reported pass numbers have the shape its
                rule gives: ``sequential`` exactly the numbers the rule implies
                from the prompt's length (the i-th open position of a block in
                pass i // k, k = block_length / denoising_steps);
                ``low_confidence_static`` k positions a pass, fewer only in
                the pass that ends a block; ``low_confidence_dynamic`` at
                least one a pass, none skipped.
  prompt_end_share  of the inner check's reading, what the prompt-end blocks
                of its kind of traffic would add: over the ``sequential``
                wave, mean |trace_logprobs - token_logprobs| (the exact state
                against the ids-only one) over ALL its tokens, times the share
                of a timed request's tokens that a prompt-end block holds; a
                log line beside ``check.limit``.

What a reply cannot show: that the k positions committed were the MOST
confident ones (the losing candidates are gone: a program that committed k
arbitrary masked positions and reported their passes truthfully reads exact
here), and the ids a stop token dropped from its block. The CPU tests, which
see the candidates, hold those (tests/test_sdar_model.py).

``correct`` is ``rollout_family``'s AND ``trace_abs <= check.limit_trace`` AND
``trace_shape``; a reply without ``output_denoise_pass`` fails the probe.
Under ``control`` the logprob check has the family's control (int8-rounded
weights), and the probe feeds the reference pass numbers of ANOTHER order (each
block's reversed): both must read not correct.
"""

from __future__ import annotations

import gc
import threading

import numpy as np

from benchlib import harness, loadgen, traffic
from benchlib.cells import rollout_family


def blocks_of(prompt_len: int, passes, B: int) -> list[list[int]]:
    """The pass numbers of a reply's generated tokens, a block of ``B``
    absolute positions at a time."""
    out: list[list[int]] = []
    at = prompt_len
    for p in passes:
        if not out or at % B == 0:
            out.append([])
        out[-1].append(int(p))
        at += 1
    return out


def shape_ok(rule: str, block: list[int], k: int) -> bool:
    """Whether one block's pass numbers have the shape ``rule`` gives at ``k``
    positions a pass."""
    if not block or min(block) < 0:
        return False
    if rule == "sequential":
        return block == [i // k for i in range(len(block))]
    counts = np.bincount(block)
    if (counts == 0).any():  # a pass that committed nothing
        return False
    if rule == "low_confidence_static":
        return bool((counts[:-1] == k).all() and 1 <= counts[-1] <= k)
    return True  # low_confidence_dynamic: at least one a pass, none skipped


def reversed_blocks(prompt_len: int, passes, B: int) -> list[int]:
    """The same pass numbers in ANOTHER order: each block's reversed (the
    control's)."""
    return [p for block in blocks_of(prompt_len, passes, B) for p in reversed(block)]


def probe(cfg: dict, p: dict, mix: dict, seed: int, devs, control: bool) -> dict:
    """Build the engine from the seed, send one wave a rule, keep the replies
    whole, and compare them with the reference's trace."""
    fam, sp, chk = p["family"], p["server"], p["check"]
    pp = chk["trace_probe"]
    B = int(cfg["assumed"]["block_length"])
    steps = int(pp["denoising_steps"])
    k = B // steps
    eng, server, make_params = rollout_family.build_server(cfg, sp, fam, p["dtype"], seed, devs, False)
    server.start()
    waves: dict[str, list[dict]] = {}
    try:
        rng = traffic.rng_for(seed, 17)
        for rule in pp["rules"]:
            prompts = [rng.integers(0, int(cfg["vocab_size"]), int(pp["prompt_len"]) + i % B).tolist() for i in range(int(pp["requests"]))]
            replies: list[dict | None] = [None] * len(prompts)

            def one(i: int, prompt: list[int], rule=rule, replies=replies) -> None:
                sampling = {**mix["sampling"], "max_new_tokens": int(pp["new_tokens"]), "remasking_strategy": rule, "denoising_steps": steps}
                status, out = loadgen.post(server.address, "/generate", {"input_ids": prompt, "sampling_params": sampling})
                replies[i] = {"prompt": prompt, "status": status, **out}

            threads = [threading.Thread(target=one, args=(i, q), daemon=True) for i, q in enumerate(prompts)]
            for t in threads:  # one held wave: every request of it in flight together
                t.start()
            for t in threads:
                t.join(600)
            waves[rule] = [r for r in replies if r is not None]
    finally:
        server.stop()
    del eng, server
    gc.collect()
    out = {"probe_requests": int(pp["requests"]) * len(pp["rules"]), "probe_failed": 0, "trace_abs": None, "trace_shape": False, "trace_abs_by_rule": {}}
    reference = rollout_family.piece(fam, "reference")
    params = make_params()
    pad_to = int(pp["pad_to"])
    shapes, errs_all, end_diffs = [], [], []
    for rule, replies in waves.items():
        errs = []
        for r in replies:
            toks, lps, passes = r.get("output_tokens") or [], r.get("output_logprobs") or [], r.get("output_denoise_pass")
            if r["status"] != 200 or len(toks) != int(pp["new_tokens"]) or len(lps) != len(toks) or not passes or len(passes) != len(toks):
                out["probe_failed"] += 1  # a reply without its trace fails the probe: it is not passed by default
                continue
            P = len(r["prompt"])
            shapes.append(all(shape_ok(rule, blk, k) for blk in blocks_of(P, passes, B)))
            told = reversed_blocks(P, passes, B) if control else passes
            ref = reference.trace_logprobs(params, cfg, r["prompt"] + toks, P, told, pad_to)
            errs.append(np.abs(np.asarray(lps, np.float64) - ref.astype(np.float64)))
            if rule == "sequential" and not control:
                blind = reference.token_logprobs(params, cfg, r["prompt"] + toks, pad_to)[P - 1 :]
                end_diffs.append(np.abs(ref.astype(np.float64) - blind.astype(np.float64)))
        out["probe_failed"] += int(pp["requests"]) - len(replies)
        if errs:
            e = np.concatenate(errs)
            out["trace_abs_by_rule"][rule] = float(e.mean())
            errs_all.append(e)
    if errs_all and not out["probe_failed"]:
        e = np.concatenate(errs_all)
        out.update(trace_abs=float(e.mean()), trace_max=float(e.max()), trace_tokens=int(e.size), trace_shape=bool(all(shapes)))
    if end_diffs:
        # what the ids-only check reads beside the arithmetic: the sum, over a probe request, of the difference the
        # prompt-end block makes; a timed request of n tokens carries that sum once, over n tokens
        out["prompt_end_abs_sum"] = float(np.mean([d.sum() for d in end_diffs]))
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
    limit = float(p["check"]["limit_trace"])
    good = res["trace_abs"] is not None and res["trace_abs"] <= limit and res["trace_shape"]
    checked = out["facts"]["check"]
    if res.get("prompt_end_abs_sum") is not None and checked.get("tokens"):
        # the inner check read ``tokens`` tokens of ``n`` requests: each request brings one prompt-end block
        res["prompt_end_share_of_mean"] = res["prompt_end_abs_sum"] * checked["n"] / checked["tokens"]
    harness.log(
        f"trace check: {res['probe_requests']} requests ({res['probe_failed']} without a whole reply), {res.get('trace_tokens', 0)} tokens: mean |logprob - "
        f"reference at the reported passes| = {res['trace_abs']} (limit {limit}; a rule at a time {res['trace_abs_by_rule']}; worst token "
        f"{res.get('trace_max')}); every block's passes have their rule's shape: {res['trace_shape']}; the prompt-end block adds "
        f"{res.get('prompt_end_abs_sum')} to a request's sum of |differences| under the ids-only reference, "
        f"{res.get('prompt_end_share_of_mean')} of the inner check's mean"
        + (" [CONTROL: each block's pass numbers reversed]" if ctx["control"] else "")
    )
    checked.update(res)
    out["correct"] = bool(out["correct"] and good)
    return out
