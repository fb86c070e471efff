"""Closed-loop load generator: a child process that never imports jax.

Rollout workers are callers that wait: each of ``clients`` group clients
sends ``group_size`` requests sharing one prompt over loopback HTTP, waits
for all of them, and sends its next group. Client threads inside the
engine's own process would share its GIL and be read as a slow server, so
this runs as ``python loadgen.py <plan.json>`` beside the serving process.

Protocol with the parent (one line each way at a time):
  child -> parent (stdout):  ``SWEPT <n_ok> <n_failed>`` after the warm-up
                             waves, then ``RUNNING`` when the clients start
  parent -> child (stdin):   ``STOP``: no client starts another group
  child -> parent (stdout):  ``STOPPING``; the parent now has the server end
                             what is in flight (a 3,000-token request would
                             cost a minute to drain), and those calls return
                             with the tokens they have and their ``ttft``
  child -> parent (stdout):  ``WROTE <path>`` after the records are written
The clock is ``time.monotonic()``, which both processes read from the same
system clock, so the parent's window marks apply to the child's stamps.
A call that has not returned ``COLLECT_S`` seconds after STOP is abandoned.
"""

from __future__ import annotations

import http.client
import json
import math
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchlib import traffic  # noqa: E402  (numpy only)

COLLECT_S = 8.0


def post(addr: str, path: str, body: dict | None, timeout: float = 600.0) -> tuple[int, dict]:
    host, port = addr.rsplit(":", 1)
    conn = http.client.HTTPConnection(host, int(port), timeout=timeout)
    try:
        if body is None:
            conn.request("GET", path)
        else:
            conn.request(
                "POST", path, json.dumps(body), {"Content-Type": "application/json"}
            )
        r = conn.getresponse()
        raw = r.read()
        return r.status, (json.loads(raw) if raw else {})
    finally:
        conn.close()


def generate(addr: str, prompt: list[int], new: int, sampling: dict) -> dict:
    """One /generate call -> a record. ``ok`` is False for an HTTP error, a
    missing token or a non-finite logprob; ``cut`` marks a call the server
    ended early with ``stop_reason`` abort (the parent's cut after the
    window), whose tokens, logprobs and ``ttft`` are sound as far as they go."""
    rec = {"t_send": time.monotonic(), "asked": new, "prompt_len": len(prompt)}
    try:
        status, out = post(
            addr,
            "/generate",
            {"input_ids": prompt, "sampling_params": {**sampling, "max_new_tokens": new}},
        )
    except (OSError, http.client.HTTPException, ValueError) as e:
        rec.update(t_done=time.monotonic(), ok=False, error=f"{type(e).__name__}: {e}")
        return rec
    rec["t_done"] = time.monotonic()
    toks = out.get("output_tokens") or []
    lps = out.get("output_logprobs") or []
    sound = status == 200 and len(lps) == len(toks) and all(isinstance(x, (int, float)) and math.isfinite(x) for x in lps)
    rec.update(
        ok=sound and len(toks) == new,
        cut=sound and len(toks) < new and out.get("stop_reason") == "abort",
        status=status,
        n_out=len(toks),
        ttft=out.get("ttft"),
        server_latency=out.get("latency"),
        stop_reason=out.get("stop_reason"),
        cached_prefix_tokens=out.get("cached_prefix_tokens", 0),
        tokens=toks,
        logprobs=lps,
    )
    return rec


def held_wave(addr: str, reqs: list[tuple[list[int], int]], sampling: dict) -> list[dict]:
    """Queue ``reqs`` behind the engine's hold fence, then release them, so
    that one admission wave sees all of them whatever the thread timing:
    the wave's prefill programs are then a function of the plan alone."""
    post(addr, "/pause_generation", {"mode": "hold"})
    out: list[dict | None] = [None] * len(reqs)

    def one(i):
        out[i] = generate(addr, reqs[i][0], reqs[i][1], sampling)

    threads = [threading.Thread(target=one, args=(i,), daemon=True) for i in range(len(reqs))]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 20.0
    while time.monotonic() < deadline:
        _, st = post(addr, "/statusz", None)
        if st.get("lifecycle", {}).get("queue_depth", 0) >= len(reqs):
            break
        time.sleep(0.02)
    post(addr, "/continue_generation", {})
    for t in threads:
        t.join(600)
    return [r if r is not None else {"ok": False, "error": "no reply"} for r in out]


def sweep_waves(plan: dict) -> list[list[tuple[list[int], int]]]:
    """The warm-up waves of a plan: for each entry, ``per_bucket`` distinct
    prompts at each listed prompt length, all in one wave. The engine splits a
    bucket's group into its batched-prefill sizes, so 15 prompts a bucket
    touch sizes 8, 4, 2 and 1."""
    rng = traffic.rng_for(0, 9)  # the same prompts in every run
    waves = []
    for w in plan.get("sweep", []):
        reqs = []
        for plen in w["prompt_lens"]:
            for _ in range(int(w["per_bucket"])):
                ids = rng.integers(0, plan["vocab"], int(plen)).tolist()
                # "repeat": the same prompt n times, as a GRPO group sends it
                reqs += [(ids, int(w["new_tokens"]))] * int(w.get("repeat", 1))
        waves.append(reqs)
    return waves


def client_loop(plan: dict, client: int, stop: threading.Event, records: list, lock, give_up: list) -> None:
    stream = traffic.GroupStream(plan["mix"], plan["seed"], client, plan["vocab"])
    sampling = plan["mix"]["sampling"]
    g = 0
    while not stop.is_set():
        grp = stream.next()
        recs: list[dict | None] = [None] * len(grp["new_tokens"])

        def one(i):
            recs[i] = generate(plan["addr"], grp["prompt"], grp["new_tokens"][i], sampling)

        ts = [threading.Thread(target=one, args=(i,), daemon=True) for i in range(len(recs))]
        for t in ts:
            t.start()
        for t in ts:
            while t.is_alive() and not (stop.is_set() and time.monotonic() > give_up[0]):
                t.join(0.1)
        with lock:
            done = [(i, r) for i, r in enumerate(recs) if r is not None]  # None: abandoned
            for n, (i, r) in enumerate(done):
                r.update(client=client, group=g, member=i)
                if n == 0:  # one copy of the prompt a group
                    r["prompt"] = grp["prompt"]
                records.append(r)
        g += 1


def main(argv: list[str]) -> int:
    with open(argv[1]) as f:
        plan = json.load(f)
    n_ok = n_bad = 0
    for wave in sweep_waves(plan):
        for r in held_wave(plan["addr"], wave, plan["mix"]["sampling"]):
            n_ok += bool(r.get("ok"))
            n_bad += not r.get("ok")
    print(f"SWEPT {n_ok} {n_bad}", flush=True)
    stop = threading.Event()
    give_up = [math.inf]  # when calls still out after STOP are abandoned
    records: list[dict] = []
    lock = threading.Lock()
    threads = [
        threading.Thread(target=client_loop, args=(plan, c, stop, records, lock, give_up), daemon=True)
        for c in range(int(plan["clients"]))
    ]
    for t in threads:
        t.start()
    print("RUNNING", flush=True)
    for line in sys.stdin:
        if line.strip() == "STOP":
            break
    give_up[0] = time.monotonic() + COLLECT_S
    stop.set()
    print("STOPPING", flush=True)
    for t in threads:
        t.join(max(0.1, give_up[0] + 1.0 - time.monotonic()))
    with lock:
        snapshot = list(records)
    with open(plan["out"], "w") as f:
        json.dump({"records": snapshot}, f)
    print(f"WROTE {plan['out']}", flush=True)
    sys.stdout.flush()
    os._exit(0)  # request threads still blocked on abandoned calls


if __name__ == "__main__":
    sys.exit(main(sys.argv))
