"""What the rows of the window's decode steps were doing, from the program's
own ledger: every ``areal.decode.pass`` of the span RECORD carries the
``steps`` the chunk it drained ran, the ``rows`` the program stepped for, the
``tokens`` it credited (the LIVE row-steps), the row-steps ``spent`` under the
device's mask on a request that had already ended and those ``dropped`` with
a request gone by the drain, so that tokens + spent + dropped == rows x steps
a pass (docs/observability.md "Spans and scopes").

The record, the window's first instant and ``window_s`` are ``span_record``'s
(the profiler session's first instant carried over to the record's clock). A
pass credits at its drain, so a pass belongs to the window its END lies in.
Speculative passes (``spec``) are one verify forward whose rows emit several
tokens: counted apart, in no share.

``value``:
  live_pct          sum tokens / (slots x sum steps) over the window's passes:
                    with steps a second, the two factors of ``rollout_tok_s``
  spent_pct         sum spent / (slots x sum steps)
  wall_ms_per_step  1000 x window_s / sum steps: what a decoding row waits a
                    token, all in (beside ``decode_step_ms``: the prompt
                    passes' and the gaps' cost a step)
The run's log has, once: passes, steps, steps a second and the row-steps by
kind; steps/s x slots x live share against the run's ``rollout_tok_s``; the
passes that handed prompts to prefill programs and their share of the window;
the mean of what admission left queued; the share of pass time held for the
commit point; and, in the traced span, the steps the program says it ran
against the chunk programs in the trace x the workload file's
``decode_steps``. None where the record's passes lack ``steps`` (the parent of
the PR that brought the ledger), or ``span_record`` finds no window; never an
error."""
import importlib.util
import os
import re

from benchlib import harness

PASS = "areal.decode.pass"
CHUNK_MODULE = re.compile(r"^jit_chunk\(")


def _span_record():
    here = os.path.dirname(os.path.abspath(__file__))
    spec = importlib.util.spec_from_file_location("benchfile_span_record", os.path.join(here, "span_record.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _log_traced_span(facts: dict, passes) -> None:
    """C2's check: the steps the passes that ended inside the traced span say
    ran, against the chunk programs the trace holds x the file's steps a call
    (what ``decode_step_ms`` divides by). The trace cuts a program at each edge."""
    tr, span = facts.get("trace"), facts.get("trace_span")
    if tr is None or not tr.devices or not span:
        return
    runs = sum(1 for name, _, _ in tr.devices[0].modules if CHUNK_MODULE.search(name))
    said = sum(e.args["steps"] for e in passes if span[0] * 1e9 < e.end_ns <= span[1] * 1e9)
    by_file = runs * int(facts["server"]["decode_steps"])
    harness.log(
        f"in the traced span the program says it ran {said} steps; the trace holds {runs} chunk programs x the file's "
        f"decode_steps {facts['server']['decode_steps']} = {by_file} ({said - by_file:+d}: the trace's edges cut a program each)"
    )


def _ledger(facts: dict) -> dict | None:
    """The window's sums (worked out, and logged, once a run: ``facts`` keeps them)."""
    if "pass_ledger" in facts:
        return facts["pass_ledger"]
    facts["pass_ledger"] = None
    sr = _span_record()
    rec = sr.the_record(facts)
    lo = None if rec is None else sr.window_start_ns(facts, rec)
    if lo is None:
        return None
    if "window_s" not in facts:
        harness.log("a run without a window of a fixed length: no decode passes to count")
        return None
    window_s = float(facts["window_s"])
    hi = lo + int(window_s * 1e9)
    every = [e for e in rec.entries if e.name == PASS and e.args]
    if not any("steps" in e.args for e in every):
        harness.log(f"the record's {PASS} carry no steps: a program without the row ledger")
        return None
    inside = [e for e in every if lo < e.end_ns <= hi and "steps" in e.args]
    spec = [e for e in inside if e.args.get("spec")]
    passes = [e for e in inside if not e.args.get("spec")]
    total = lambda key, es=passes: sum(int(e.args.get(key, 0)) for e in es)  # noqa: E731
    steps, tokens, spent, dropped = total("steps"), total("tokens"), total("spent"), total("dropped")
    stepped = sum(e.args["rows"] * e.args["steps"] for e in passes)  # row-steps under a request's row
    if steps <= 0:
        harness.log(f"no decode step ended inside the window of {window_s:.3f} s ({len(inside)} passes, {len(spec)} speculative)")
        return None
    slots = int(facts["server"]["slots"])
    whole = slots * steps
    out = {"live_pct": 100.0 * tokens / whole, "spent_pct": 100.0 * spent / whole, "wall_ms_per_step": 1e3 * window_s / steps}
    facts["pass_ledger"] = out
    harness.log(
        f"{len(passes)} x {PASS} ended inside the window of {window_s:.3f} s: {steps} steps ({steps / window_s:.2f} a second) x {slots} slots = {whole} row-steps: "
        f"live {tokens} ({out['live_pct']:.2f}%), spent {spent} ({out['spent_pct']:.2f}%), dropped {dropped} ({100.0 * dropped / whole:.2f}%), "
        f"empty {whole - stepped} ({100.0 * (whole - stepped) / whole:.2f}%); tokens + spent + dropped - rows x steps = {tokens + spent + dropped - stepped}"
    )
    by_ledger = steps / window_s * slots * out["live_pct"] / 100.0
    said = (facts.get("values") or {}).get("rollout_tok_s")
    harness.log(
        f"steps/s x slots x live share = {steps / window_s:.3f} x {slots} x {out['live_pct'] / 100.0:.4f} = {by_ledger:.1f} tokens/s"
        + (f"; the run's rollout_tok_s {said:.1f} ({100.0 * (by_ledger / said - 1):+.2f}%: the two windows' edges lie a pass apart)" if said else "")
    )
    dur = lambda es: sum(e.end_ns - e.start_ns for e in es) * 1e-9  # noqa: E731
    prompt = [e for e in passes if e.args.get("prompt_tokens", 0) > 0]
    harness.log(
        f"{len(prompt)} passes handed {total('prompt_tokens')} prompt tokens to prefill programs ({total('admitted')} requests given a slot): "
        f"{dur(prompt):.3f} s, {100.0 * dur(prompt) / window_s:.2f}% of the window; mean queued after admission {total('queued') / len(passes):.3f}; "
        f"held for the commit point {100.0 * total('held_us') * 1e-6 / dur(passes):.2f}% of pass time"
    )
    if spec:
        harness.log(f"{len(spec)} speculative passes apart: {total('steps', spec)} rounds, {total('tokens', spec)} tokens, {dur(spec):.3f} s")
    _log_traced_span(facts, passes)
    return out


def read(metric: dict, facts: dict):
    if facts.get("trace") is None:
        return None
    led = _ledger(facts)
    return None if led is None else led[metric["value"]]
