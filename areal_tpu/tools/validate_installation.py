"""Installation validator (reference areal/tools/validate_installation.py):
checks imports, device availability, a tiny jit, and the HTTP stack; prints
a PASS/FAIL table and exits nonzero on failure.

``--chaos-self-test`` additionally spins up a 3-replica in-process
inference fleet (tiny model, CPU) behind a seeded FaultInjector dropping
10% of requests, and asserts a rollout batch completes through the
retrying transport — a one-command smoke test of the fault-tolerance
layer for CI.

``--overload-self-test`` drives a small in-process fleet at ~2x its
sustained capacity with the chaos stall injector running, and asserts the
overload-safety contract (docs/request_lifecycle.md): shed requests get
clean 429 + Retry-After, admitted work keeps a bounded p99, the deadline
reaper fires on the flood, and the PagePool ends with zero leaked pages.

``--train-obs-self-test`` runs a short synchronous-mode CPU RL loop under
a chaos-throttled rollout and asserts the trainer goodput observatory
(docs/observability.md "Trainer observatory"): the step-phase breakdown
identity with >= 90% measured named-phase coverage, a non-zero measured
rollout_wait bubble, a populated HBM ledger (analytic CPU fallback), and
live XLA compile counters.

``--learning-obs-self-test`` runs a short CPU RL loop with FORCED
staleness (eta > 0: the rollout pipeline pre-generates several versions
ahead of the trainer) and asserts the learning-health observatory
(docs/observability.md "Learning-health observatory"): the high-lag
bucket shows strictly higher measured behave-|KL| than lag-0, the behave
importance-weight cap leaves a non-zero cap-hit tail, and the trajectory
lineage ring joins journal frames to training-step loss stats by trace
id (generate -> journal -> consume -> update for one task id).

``--routing-self-test`` drives a 3-replica in-process fleet under seeded
chaos with an 80%-shared-prefix multi-turn workload through BOTH routing
policies (docs/serving.md "Cache-aware routing"), and asserts the routing
brain end to end: cache-aware measurably raises warm suffix-only prefill
(radix hit tokens) over round-robin, every decision lands in the flight
ring with a reason, and an evict -> respawn cycle yields zero routes to
the evicted replica while it is down (with its shadow prefix index read
as cold after the rejoin).

``--gateway-tier-self-test`` stands up the horizontally-sharded gateway
tier (docs/serving.md "Gateway tier"): 3 gateway shards over a small
in-process fleet, driven by the ring-hashing tier client while seeded
chaos kills one shard mid-run. Asserts the tier's whole fault story:
every session completes or terminates with a real terminal status (zero
responseless requests), the clients re-hash their sessions onto the
surviving shards (failovers observed, the keyspace the victim owned is
served by survivors), and the membership view converges to the two
survivors.

``--spec-decode-self-test`` runs a spec-enabled tiny engine over an
acceptance-friendly repetitive workload (docs/serving.md "Speculative
decoding") and asserts acceptance rate > 0, zero leaked KV pages after
settling, draft/verify stage coverage in the request timelines, and the
kernel probe's exact-sum identity over the widened phase vocabulary.

``--kernelcheck`` runs every registered ops/ Pallas kernel's full
differential case grid in interpret mode against its XLA reference
(docs/perf.md "Paged suffix-attention kernel family") — the numerics
companion to the always-on static ``kernel_lint`` check.

Usage: python -m areal_tpu.tools.validate_installation [--tpu]
    [--chaos-self-test] [--overload-self-test] [--timeline-self-test] [--train-obs-self-test]
    [--learning-obs-self-test] [--preemption-self-test] [--routing-self-test]
    [--spec-decode-self-test]
    [--gateway-tier-self-test] [--kernelcheck]
"""

from __future__ import annotations

import argparse
import sys


def tiny_model_config():
    """The toy model every in-process self-test fleet serves (shared with
    tools/bench_gateway's LocalFleet — one definition, or the self-tests
    and the bench silently measure different models)."""
    from areal_tpu.models import qwen

    return qwen.ModelConfig(
        vocab_size=128,
        hidden_size=32,
        intermediate_size=64,
        num_layers=2,
        num_heads=2,
        num_kv_heads=1,
        dtype="float32",
        tie_word_embeddings=True,
        rope_theta=10000.0,
    )


def _check(name, fn, results):
    try:
        detail = fn() or ""
        results.append((name, True, str(detail)))
    except Exception as e:  # noqa: BLE001
        results.append((name, False, f"{type(e).__name__}: {e}"))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--tpu", action="store_true", help="require a TPU backend")
    p.add_argument(
        "--chaos-self-test",
        action="store_true",
        help="run a 3-replica local fleet under 10%% injected faults and "
        "assert a rollout batch completes",
    )
    p.add_argument(
        "--overload-self-test",
        action="store_true",
        help="drive a small local fleet at ~2x sustained capacity with "
        "chaos stalls and assert overload safety: clean 429 + Retry-After "
        "for shed work, bounded p99 for admitted work, deadline reaping, "
        "and zero leaked KV pages",
    )
    p.add_argument(
        "--timeline-self-test",
        action="store_true",
        help="run a short serve (incl. a weight-commit hold fence) and "
        "assert the request-timeline observatory: stage sums ≈ wall time "
        "per request, fence stalls attributed, and zero unterminated "
        "timelines",
    )
    p.add_argument(
        "--train-obs-self-test",
        action="store_true",
        help="run a short CPU RL loop under a throttled rollout and assert "
        "the trainer goodput observatory: step-phase breakdown sums to the "
        "step wall time with >= 90%% named-phase coverage, non-zero "
        "rollout_wait (the async bubble), and a populated HBM ledger",
    )
    p.add_argument(
        "--routing-self-test",
        action="store_true",
        help="3-replica fleet under seeded chaos: cache-aware routing "
        "must raise warm suffix-only prefill vs round-robin, audit every "
        "decision to the flight ring, and never route to an evicted "
        "replica (docs/serving.md)",
    )
    p.add_argument(
        "--autopilot-self-test",
        action="store_true",
        help="seeded chaos-stall fleet at ~2x gateway capacity with the "
        "goodput autopilot on: the admission controller must widen the "
        "interactive headroom, the interactive shed rate must drop in the "
        "second measured window, and every setpoint change must be "
        "auditable in the flight ring (docs/autopilot.md) — all on CPU",
    )
    p.add_argument(
        "--learning-obs-self-test",
        action="store_true",
        help="short CPU RL run with forced staleness (eta>0) asserting "
        "the learning-health observatory: high-lag behave-|KL| strictly "
        "above lag-0, non-zero behave-cap tail mass, and lineage records "
        "joining journal frames to step loss stats by trace id — all "
        "measured, deterministic under seeded chaos",
    )
    p.add_argument(
        "--kernelcheck",
        action="store_true",
        help="run every registered ops/ Pallas kernel's full kernelcheck "
        "case grid (interpret mode vs XLA reference — for "
        "paged_suffix_attention: GQA ratios x ragged lengths x "
        "bf16/int8/fp8 x chain/tree masks) and fail on any divergence; "
        "the numerics companion to the static kernel_lint check "
        "(docs/perf.md 'Paged suffix-attention kernel family')",
    )
    p.add_argument(
        "--spec-decode-self-test",
        action="store_true",
        help="run a spec-enabled tiny engine over a repetitive workload "
        "and assert the speculative-decoding contract: acceptance rate "
        "> 0, zero leaked KV pages after settling, draft/verify stages "
        "in the request timelines, and the kernel probe's exact-sum "
        "identity over the widened phase vocabulary",
    )
    p.add_argument(
        "--gateway-tier-self-test",
        action="store_true",
        help="3 gateway shards over a small fleet under seeded chaos: one "
        "shard is killed mid-run and every session must complete or "
        "terminate with a real terminal (zero responseless requests) "
        "while the survivors absorb the re-hashed load "
        "(docs/serving.md 'Gateway tier')",
    )
    p.add_argument(
        "--preemption-self-test",
        action="store_true",
        help="run a tiny CPU fleet + trainer, deliver a REAL SIGTERM "
        "mid-step, and assert the preemption contract: trainer emergency-"
        "dumps and exits cleanly, the replica drains (0 leaked pages, all "
        "timelines terminated), a relaunch replays journaled trajectories, "
        "and the async save path pauses the step loop <= 1/5 of a sync save",
    )
    args = p.parse_args(argv)
    results: list[tuple[str, bool, str]] = []

    def imports():
        import aiohttp  # noqa: F401
        import flax  # noqa: F401
        import optax  # noqa: F401
        import orbax.checkpoint  # noqa: F401
        import transformers  # noqa: F401

        import areal_tpu  # noqa: F401

        return "core deps + areal_tpu"

    _check("imports", imports, results)

    def devices():
        import jax

        devs = jax.devices()
        if args.tpu and devs[0].platform != "tpu":
            raise RuntimeError(f"expected tpu, got {devs[0].platform}")
        return f"{len(devs)}x {devs[0].platform}"

    _check("devices", devices, results)

    def tiny_jit():
        import jax
        import jax.numpy as jnp

        y = jax.jit(lambda x: (x @ x).sum())(jnp.ones((128, 128), jnp.bfloat16))
        return f"jit ok ({float(y):.0f})"

    _check("jit", tiny_jit, results)

    def engine_contract():
        from areal_tpu.api.engine_api import InferenceEngine, TrainEngine
        from areal_tpu.engine.train_engine import JaxTrainEngine
        from areal_tpu.inference.client import RemoteJaxEngine

        assert issubclass(JaxTrainEngine, TrainEngine)
        assert issubclass(RemoteJaxEngine, InferenceEngine)
        return "contracts wired"

    _check("contracts", engine_contract, results)

    def metrics_lint():
        """Static metric-name lint is arealint's OBS family now (one source
        of truth: registration outside the catalog, naming convention,
        missing help, duplicate names, dangling references). Here we invoke
        it over the package, then keep the one check that is inherently
        runtime: the registry's Prometheus rendering must round-trip
        through its own parser."""
        from areal_tpu.analysis import (
            default_baseline_path,
            default_package_root,
            run_analysis,
        )
        from areal_tpu.observability import catalog
        from areal_tpu.observability.metrics import (
            Registry,
            parse_prometheus_text,
        )

        res = run_analysis(
            [default_package_root()],
            rules=["OBS"],
            baseline_path=default_baseline_path(),
        )
        if not res.ok:
            raise RuntimeError(
                "; ".join(f.render() for f in res.findings[:5])
                + (f" (+{len(res.findings) - 5} more)" if len(res.findings) > 5 else "")
            )
        reg = catalog.register_all(Registry())
        parse_prometheus_text(reg.render_prometheus())
        return (
            f"arealint OBS clean over {res.files_checked} files; "
            f"{len(reg.families())} families render round-trip"
        )

    _check("metrics", metrics_lint, results)

    def perf_lint():
        """The dataflow-aware performance families (PRF hot-path syncs,
        DON donation, SHD sharding specs, RCP recompile risk) over the
        package, against the checked-in baseline — the static half of
        what the PR 9 observatory measures at runtime
        (docs/static_analysis.md)."""
        from areal_tpu.analysis import (
            default_baseline_path,
            default_package_root,
            run_analysis,
        )

        res = run_analysis(
            [default_package_root()],
            rules=["PRF", "DON", "SHD", "RCP"],
            baseline_path=default_baseline_path(),
        )
        if not res.ok:
            raise RuntimeError(
                "; ".join(f.render() for f in res.findings[:5])
                + (f" (+{len(res.findings) - 5} more)" if len(res.findings) > 5 else "")
            )
        return (
            f"PRF/DON/SHD/RCP clean over {res.files_checked} files "
            f"({len(res.suppressed)} reasoned suppressions)"
        )

    _check("perf_lint", perf_lint, results)

    def wire_lint():
        """The distributed-control-plane families (WIRE wire-contract
        drift between the HTTP-coupled processes, LCK lock/fence
        ordering in the threaded engine) over the package — the static
        half of what the scale-out e2e tests exercise at runtime
        (docs/static_analysis.md)."""
        from areal_tpu.analysis import (
            default_baseline_path,
            default_package_root,
            run_analysis,
        )

        res = run_analysis(
            [default_package_root()],
            rules=["WIRE", "LCK"],
            baseline_path=default_baseline_path(),
        )
        if not res.ok:
            raise RuntimeError(
                "; ".join(f.render() for f in res.findings[:5])
                + (f" (+{len(res.findings) - 5} more)" if len(res.findings) > 5 else "")
            )
        return (
            f"WIRE/LCK clean over {res.files_checked} files "
            f"({len(res.suppressed)} reasoned suppressions)"
        )

    _check("wire_lint", wire_lint, results)

    def kernel_lint():
        """The kernel-arc families (KRN Pallas launch-site safety, PVT
        private-jax signature pins re-verified against the INSTALLED jax,
        MSH collective/mesh consistency) over the package — run on the
        deployment's actual jax, this is the install-time check that a
        jax bump has not drifted any pinned private kernel signature
        (docs/static_analysis.md)."""
        from areal_tpu.analysis import (
            default_baseline_path,
            default_package_root,
            run_analysis,
        )

        res = run_analysis(
            [default_package_root()],
            rules=["KRN", "PVT", "MSH"],
            baseline_path=default_baseline_path(),
        )
        if not res.ok:
            raise RuntimeError(
                "; ".join(f.render() for f in res.findings[:5])
                + (f" (+{len(res.findings) - 5} more)" if len(res.findings) > 5 else "")
            )
        return (
            f"KRN/PVT/MSH clean over {res.files_checked} files "
            f"({len(res.suppressed)} reasoned suppressions)"
        )

    _check("kernel_lint", kernel_lint, results)

    def native_kernels():
        from areal_tpu.native import datapack_lib
        from areal_tpu.utils.datapack import ffd_allocate

        lib = datapack_lib()
        bins = ffd_allocate(list(range(1, 200)), capacity=512)
        assert sorted(i for b in bins for i in b) == list(range(199))
        return "C++ datapack" if lib is not None else "python fallback (no g++?)"

    _check("native", native_kernels, results)

    if args.kernelcheck:

        def kernelcheck():
            from areal_tpu.tools.kernelcheck import run_all

            recs = run_all()
            bad = [r for r in recs if not r["ok"]]
            if bad:
                raise RuntimeError(
                    "; ".join(
                        f"{r['kernel']}[{r['case']}]: "
                        + r.get("error", f"diff {r.get('max_abs_diff')}")
                        for r in bad[:5]
                    )
                    + (f" (+{len(bad) - 5} more)" if len(bad) > 5 else "")
                )
            kernels = sorted({r["kernel"] for r in recs})
            return f"{len(recs)} cases green over {len(kernels)} kernels"

        _check("kernelcheck", kernelcheck, results)

    if args.chaos_self_test:
        _check("chaos", chaos_self_test, results)

    if args.overload_self_test:
        _check("overload", overload_self_test, results)

    if args.timeline_self_test:
        _check("timeline", timeline_self_test, results)

    if args.train_obs_self_test:
        _check("train_obs", train_obs_self_test, results)

    if args.learning_obs_self_test:
        _check("learning_obs", learning_obs_self_test, results)

    if args.preemption_self_test:
        _check("preemption", preemption_self_test, results)

    if args.routing_self_test:
        _check("routing", routing_self_test, results)

    if args.autopilot_self_test:
        _check("autopilot", autopilot_self_test, results)

    if args.spec_decode_self_test:
        _check("spec_decode", spec_decode_self_test, results)

    if args.gateway_tier_self_test:
        _check("gateway_tier", gateway_tier_self_test, results)

    width = max(len(n) for n, _, _ in results)
    ok = True
    for name, passed, detail in results:
        ok &= passed
        print(f"{name:<{width}}  {'PASS' if passed else 'FAIL'}  {detail}")
    return 0 if ok else 1


def chaos_self_test(
    n_replicas: int = 3, drop_prob: float = 0.1, n_prompts: int = 6, seed: int = 42
) -> str:
    """3-replica in-process fleet + seeded 10%-drop FaultInjector: a rollout
    batch must complete through retries/failover, and the chaos harness must
    actually have fired (otherwise the test proves nothing)."""
    import jax

    from areal_tpu.api.config import (
        ChaosConfig,
        FaultToleranceConfig,
        InferenceEngineConfig,
        MeshConfig,
        ServerConfig,
    )
    from areal_tpu.api.io_struct import GenerationHyperparameters
    from areal_tpu.inference.client import RemoteJaxEngine
    from areal_tpu.inference.decode_engine import DecodeEngine
    from areal_tpu.inference.server import ServerThread
    from areal_tpu.models import qwen
    from areal_tpu.robustness import FaultInjector
    from areal_tpu.workflow.rlvr import RLVRWorkflow

    tiny = tiny_model_config()
    params = qwen.init_params(jax.random.PRNGKey(0), tiny)
    servers = []
    client = None
    try:
        for i in range(n_replicas):
            cfg = ServerConfig(
                max_batch_size=4,
                max_seq_len=64,
                decode_steps_per_call=4,
                seed=i,
                mesh=MeshConfig(data=-1, fsdp=1, seq=1, model=1),
            )
            eng = DecodeEngine(cfg, params=params, model_cfg=tiny)
            eng.initialize()
            st = ServerThread(cfg, eng)
            st.start()
            servers.append(st)
        client = RemoteJaxEngine(
            InferenceEngineConfig(
                max_concurrent_rollouts=4,
                consumer_batch_size=2,
                max_head_offpolicyness=100,
                request_timeout=60,
                request_retries=5,
                fault_tolerance=FaultToleranceConfig(
                    backoff_base_s=0.05, backoff_max_s=0.5
                ),
            ),
            addresses=[s.address for s in servers],
        )
        client.initialize()
        injector = FaultInjector(
            ChaosConfig(enabled=True, seed=seed, drop_prob=drop_prob)
        )
        client.install_fault_injector(injector)
        wf = RLVRWorkflow(
            lambda *a, **k: 1.0,
            GenerationHyperparameters(max_new_tokens=4, greedy=True),
        )
        batch = client.rollout_batch(
            [{"prompt_ids": [2 + i, 5, 7]} for i in range(n_prompts)],
            workflow=wf,
        )
        assert batch["input_ids"].shape[0] == n_prompts, batch["input_ids"].shape
        stats = injector.stats()
        assert stats["drop"] > 0, "fault injector never fired"
        return (
            f"{n_prompts} rollouts over {n_replicas} replicas survived "
            f"{stats['drop']} injected drops ({stats['requests_seen']} requests)"
        )
    finally:
        if client is not None:
            client.destroy()
        for st in servers:
            st.stop()


def overload_self_test(
    n_interactive: int = 4,
    n_flood: int = 6,
    flood_deadline_s: float = 2.0,
    p99_bound_s: float = 60.0,
    seed: int = 99,
) -> str:
    """One lifecycle-enabled server (2 slots, queue cap 3) driven at ~2x
    sustained capacity — a flood of effectively-unbounded generations on
    short deadlines rides alongside short interactive requests, with the
    chaos stall injector perturbing every post. Asserts the overload
    contract end to end; the tier-1 acceptance test
    (tests/test_request_lifecycle.py::test_overload_acceptance) adds the
    greedy byte-identity check against a lifecycle-disabled twin."""
    import asyncio
    import time

    import aiohttp
    import jax

    from areal_tpu.api.config import (
        ChaosConfig,
        MeshConfig,
        RequestLifecycleConfig,
        ServerConfig,
    )
    from areal_tpu.inference.decode_engine import DecodeEngine
    from areal_tpu.inference.server import ServerThread
    from areal_tpu.models import qwen
    from areal_tpu.robustness import FaultInjector

    tiny = tiny_model_config()
    params = qwen.init_params(jax.random.PRNGKey(0), tiny)
    cfg = ServerConfig(
        max_batch_size=2,
        max_seq_len=256,
        decode_steps_per_call=4,
        mesh=MeshConfig(data=-1, fsdp=1, seq=1, model=1),
        lifecycle=RequestLifecycleConfig(
            max_queue_depth=3, retry_after_s=0.1, watchdog_s=30.0
        ),
    )
    eng = DecodeEngine(cfg, params=params, model_cfg=tiny)
    eng.initialize()
    srv = ServerThread(cfg, eng)
    srv.start()
    inj = FaultInjector(
        ChaosConfig(enabled=True, seed=seed, stall_prob=0.3, stall_s=0.15)
    )
    stats = {"s429": 0, "latency": []}

    async def one(i: int, ids, n_new: int, deadline_s: float | None, tag: str):
        payload = {
            "input_ids": ids,
            "rid": f"{tag}-{i}",
            "sampling_params": {"max_new_tokens": n_new, "greedy": True},
        }
        headers = {}
        if deadline_s is not None:
            from areal_tpu.api import wire

            headers[wire.DEADLINE_HEADER] = f"{time.time() + deadline_s:.6f}"
        t0 = time.monotonic()
        async with aiohttp.ClientSession() as s:
            for _ in range(200):  # bounded retry: no hung client
                await inj.aperturb(srv.address, "/generate")
                async with s.post(
                    f"http://{srv.address}/generate",
                    json=payload,
                    headers=headers,
                ) as r:
                    if r.status == 429:
                        stats["s429"] += 1
                        ra = r.headers.get("Retry-After")
                        if ra is None or float(ra) <= 0:
                            raise AssertionError("429 without Retry-After")
                        await asyncio.sleep(float(ra))
                        continue
                    assert r.status == 200, await r.text()
                    await r.json()
                    break
            else:
                raise AssertionError("client starved: 200 rejections")
        if tag == "interactive":
            stats["latency"].append(time.monotonic() - t0)

    async def drive():
        # 2 slots + queue cap 3 vs. n_interactive + n_flood concurrent
        # requests (the flood ignores EOS) = ~2x sustained capacity
        await asyncio.gather(
            *[
                one(i, [3 + i, 14 + i, 15], 8, None, "interactive")
                for i in range(n_interactive)
            ],
            *[
                one(i, [40 + i, 2, 2], 100_000, flood_deadline_s, "flood")
                for i in range(n_flood)
            ],
        )

    try:
        asyncio.run(drive())
        if stats["s429"] == 0:
            raise AssertionError("overload never shed — not a 2x run")
        p99 = max(stats["latency"])  # == p99 at this sample count
        if p99 >= p99_bound_s:
            raise AssertionError(f"admitted p99 {p99:.1f}s >= {p99_bound_s}s")
        if eng.stats["deadline_exceeded"] == 0:
            raise AssertionError("deadline reaper never fired on the flood")
        if inj.stats()["stall"] == 0:
            raise AssertionError("chaos stalls never fired")
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            snap = eng.admission_snapshot()
            if snap["queue_depth"] == 0 and snap["active_slots"] == 0:
                break
            time.sleep(0.05)
        held = (
            eng.prefix_cache_stats()["pages_held"]
            if eng.slots.radix is not None
            else 0
        )
        leaked = eng.slots.pool.used - held
        if leaked != 0:
            raise AssertionError(f"{leaked} KV pages leaked after overload")
        return (
            f"{n_interactive}+{n_flood} reqs @2x: {stats['s429']} clean 429s, "
            f"admitted p99 {p99:.1f}s, "
            f"{eng.stats['deadline_exceeded']} deadline reaps, "
            f"{inj.stats()['stall']} stalls, 0 leaked pages"
        )
    finally:
        srv.stop()


def timeline_self_test(
    n_short: int = 4, coverage_floor: float = 0.5
) -> str:
    """Short serve over one tiny engine asserting the request-timeline
    observatory end to end (docs/observability.md "Request timelines"):

    - every request's named stages (queue_wait + prefill + decode +
      fence_stall) cover >= ``coverage_floor`` of its wall time — i.e.
      the explicit ``other_s`` residual is small, so timelines actually
      attribute latency instead of hiding it;
    - a weight-commit hold fence mid-decode lands in ``fence_stall_s``;
    - zero unterminated timelines once the engine drains (every request
      that entered the engine left through a terminal stage)."""
    import threading
    import time

    import jax

    from areal_tpu.api.config import MeshConfig, ServerConfig
    from areal_tpu.api.io_struct import (
        GenerationHyperparameters,
        ModelRequest,
    )
    from areal_tpu.inference.decode_engine import DecodeEngine
    from areal_tpu.models import qwen

    tiny = tiny_model_config()
    params = qwen.init_params(jax.random.PRNGKey(0), tiny)
    cfg = ServerConfig(
        max_batch_size=4,
        max_seq_len=256,
        decode_steps_per_call=4,
        seed=1,
        mesh=MeshConfig(data=-1, fsdp=1, seq=1, model=1),
    )
    eng = DecodeEngine(cfg, params=params, model_cfg=tiny)
    eng.initialize()
    eng.start()
    try:
        # short mixed-priority wave (warms the compiled programs too, so
        # the fence request below measures serving, not compilation)
        for i in range(n_short):
            resp = eng.generate_sync(
                ModelRequest(
                    input_ids=[3 + i, 7, 9],
                    gconfig=GenerationHyperparameters(
                        max_new_tokens=8, greedy=True
                    ),
                    metadata={"priority": "rollout" if i % 2 else "interactive"},
                ),
                timeout=120,
            )
            assert resp.queue_wait_s >= 0 and resp.decode_s >= 0
        # long request with a hold fence dropped mid-decode
        done = threading.Event()
        box = []
        eng.submit(
            ModelRequest(
                input_ids=[5, 6, 7],
                gconfig=GenerationHyperparameters(
                    max_new_tokens=200, greedy=True, ignore_eos=True
                ),
            ),
            lambda r: (box.append(r), done.set()),
        )
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if any(
                t is not None and t.out_tokens for t in eng._slot_task
            ):
                break
            time.sleep(0.01)
        eng.pause_generation(mode="hold")
        eng.wait_fence_ack(10.0)
        time.sleep(0.4)  # the measurable stall
        eng.continue_generation()
        assert done.wait(120), "fence request never completed"
        fenced = box[0]
        if fenced.fence_stall_s < 0.2:
            raise AssertionError(
                f"hold fence not attributed: fence_stall_s="
                f"{fenced.fence_stall_s:.3f}s (held ~0.4s)"
            )
        # settle, then audit the recorder
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            snap = eng.admission_snapshot()
            if snap["queue_depth"] == 0 and snap["active_slots"] == 0:
                break
            time.sleep(0.05)
        stats = eng.timeline.stats()
        if stats["unterminated"] != 0:
            raise AssertionError(
                f"{stats['unterminated']} unterminated timelines "
                f"(started {stats['started']}, completed {stats['completed']})"
            )
        worst, n_audited = 1.0, 0
        for rec in eng.timeline.recent():
            bd = rec["breakdown"]
            if bd["total_s"] <= 0 or rec["terminal_reason"] not in (
                "stop",
                "length",
            ):
                continue
            n_audited += 1
            covered = 1.0 - bd["other_s"] / bd["total_s"]
            worst = min(worst, covered)
        if n_audited == 0:
            raise AssertionError("no completed timelines to audit")
        if worst < coverage_floor:
            raise AssertionError(
                f"stage coverage {worst:.0%} < {coverage_floor:.0%} of "
                "wall time — timelines are not attributing latency"
            )
        return (
            f"{stats['completed']} timelines terminated cleanly, stage "
            f"coverage >= {worst:.0%}, fence stall "
            f"{fenced.fence_stall_s:.2f}s attributed"
        )
    finally:
        eng.stop()


def train_obs_self_test(
    n_steps: int = 2, coverage_floor: float = 0.9, stall_s: float = 0.1
) -> str:
    """Short CPU RL run asserting the trainer goodput observatory
    (docs/observability.md "Trainer observatory") with MEASURED numbers:

    - every completed step's phase breakdown satisfies the identity
      (named phases + other_s == step wall time) and the named phases
      cover >= ``coverage_floor`` of it — the residual attributes, it
      doesn't hide;
    - rollout_wait is non-zero under a throttled rollout (a seeded chaos
      stall injector on every client POST — the async bubble measured,
      not mocked);
    - the trainer HBM ledger itemizes params + optimizer state (analytic
      CPU fallback) and the XLA compile counters saw this run's compiles.
    """
    import jax
    import numpy as np

    from areal_tpu.api.config import (
        ChaosConfig,
        DatasetConfig,
        InferenceEngineConfig,
        MeshConfig,
        MicroBatchSpec,
        OptimizerConfig,
        PPOActorConfig,
        PPOConfig,
        RecoverConfig,
        SaverConfig,
        ServerConfig,
        StatsLoggerConfig,
    )
    from areal_tpu.api.io_struct import FinetuneSpec, GenerationHyperparameters
    from areal_tpu.engine.train_engine import JaxTrainEngine
    from areal_tpu.inference.client import RemoteJaxEngine
    from areal_tpu.inference.decode_engine import DecodeEngine
    from areal_tpu.inference.server import ServerThread
    from areal_tpu.models import qwen
    from areal_tpu.robustness import FaultInjector
    from areal_tpu.trainer.rl_trainer import PPOTrainer
    from areal_tpu.utils.compile_cache import compile_stats
    from areal_tpu.workflow.rlvr import RLVRWorkflow

    import tempfile

    root = tempfile.mkdtemp(prefix="areal_train_obs_selftest_")
    tiny = tiny_model_config()
    actor_cfg = PPOActorConfig(
        init_from_scratch=True,
        dtype="float32",
        param_dtype="float32",
        mesh=MeshConfig(data=-1, fsdp=1, seq=1, model=1),
        optimizer=OptimizerConfig(lr=1e-3, lr_scheduler_type="constant"),
        mb_spec=MicroBatchSpec(max_tokens_per_mb=100_000),
        bucket_step=64,
        group_size=1,
        ppo_n_minibatches=1,
        adv_norm=None,
        kl_ctl=0.0,
        use_decoupled_loss=False,
        recompute_logprob=False,
    )
    engine = JaxTrainEngine(actor_cfg, model_config=tiny)
    engine.initialize(FinetuneSpec(1, 16, 2))
    scfg = ServerConfig(
        max_batch_size=4,
        max_seq_len=128,
        decode_steps_per_call=4,
        seed=0,
        mesh=MeshConfig(data=-1, fsdp=1, seq=1, model=1),
    )
    dec = DecodeEngine(
        scfg, params=jax.tree.map(np.asarray, engine.params), model_cfg=tiny
    )
    dec.initialize()
    server = ServerThread(scfg, dec)
    server.start()
    rollout = RemoteJaxEngine(
        InferenceEngineConfig(
            max_concurrent_rollouts=4,
            consumer_batch_size=2,
            # SYNCHRONOUS mode: with any lookahead the async pipeline
            # pre-generates the next batch during this step's compute and
            # the bubble (correctly!) vanishes — offpolicyness 0 forces
            # every step to sit in prepare_batch so the test can assert
            # the bubble is MEASURED, not merely absent
            max_head_offpolicyness=0,
            request_timeout=120,
        ),
        addresses=[server.address],
    )
    rollout.initialize()
    # the throttle: every client POST eats a deterministic stall, so the
    # prepare_batch wait (rollout_wait) is guaranteed measurable
    rollout.install_fault_injector(
        FaultInjector(
            ChaosConfig(enabled=True, seed=7, stall_prob=1.0, stall_s=stall_s)
        )
    )
    cfg = PPOConfig(
        experiment_name="train-obs",
        trial_name="t0",
        total_train_epochs=50,
        total_train_steps=n_steps,
        weight_update_mode="mem",
        gconfig=GenerationHyperparameters(
            n_samples=1, max_new_tokens=4, greedy=True
        ),
        train_dataset=DatasetConfig(batch_size=2, shuffle=True),
        actor=actor_cfg,
        saver=SaverConfig(fileroot=root),
        checkpointer=SaverConfig(fileroot=root),
        recover=RecoverConfig(mode="disabled", fileroot=root),
        stats_logger=StatsLoggerConfig(fileroot=root),
    )
    cfg.evaluator.fileroot = root
    cfg.cluster.fileroot = root
    rng = np.random.default_rng(0)
    dataset = [
        {"prompt_ids": rng.integers(2, 100, 3).tolist()} for _ in range(16)
    ]
    wf = RLVRWorkflow(
        lambda *a, **k: 1.0,
        GenerationHyperparameters(max_new_tokens=4, greedy=True),
    )
    trainer = PPOTrainer(cfg, dataset, rollout=rollout, actor_engine=engine)
    try:
        c0 = compile_stats()["compiles"]
        trainer.train(workflow=wf)
        recent = trainer.step_recorder.recent()
        if len(recent) < n_steps:
            raise AssertionError(
                f"{len(recent)} step timelines recorded, expected {n_steps}"
            )
        worst_cov, min_wait = 1.0, float("inf")
        from areal_tpu.observability.step_timeline import PHASES

        for rec in recent:
            bd = rec["breakdown"]
            named = sum(bd[f"{p}_s"] for p in PHASES)
            if abs(named + bd["other_s"] - bd["total_s"]) > 1e-6:
                raise AssertionError(
                    f"breakdown identity violated at step {rec['step']}: "
                    f"{named + bd['other_s']:.6f} != {bd['total_s']:.6f}"
                )
            worst_cov = min(worst_cov, named / bd["total_s"])
            min_wait = min(min_wait, bd["rollout_wait_s"])
        if worst_cov < coverage_floor:
            raise AssertionError(
                f"phase coverage {worst_cov:.0%} < {coverage_floor:.0%} of "
                "step wall time — the timeline is not attributing latency"
            )
        if min_wait < stall_s / 2:
            raise AssertionError(
                f"rollout_wait {min_wait * 1e3:.0f}ms under a throttled "
                "rollout — the async bubble is not being measured"
            )
        ledger = trainer.last_hbm_ledger
        if ledger is None:
            raise AssertionError("no HBM ledger recorded")
        comp = ledger["components"]
        if comp.get("params", 0) <= 0 or comp.get("opt_state", 0) <= 0:
            raise AssertionError(f"HBM ledger not itemized: {comp}")
        if ledger["bytes_in_use"] <= 0:
            raise AssertionError("HBM ledger has no in-use accounting")
        compiled = compile_stats()["compiles"] - c0
        if compiled <= 0:
            raise AssertionError("compile counters saw no XLA compiles")
        bubbles = [r["breakdown"]["bubble_fraction"] for r in recent]
        return (
            f"{len(recent)} steps: phase coverage >= {worst_cov:.0%}, "
            f"bubble {min(bubbles):.0%}..{max(bubbles):.0%} "
            f"(rollout_wait >= {min_wait * 1e3:.0f}ms under throttle), "
            f"hbm ledger params {comp['params'] / 1e3:.0f}kB + opt "
            f"{comp['opt_state'] / 1e3:.0f}kB ({ledger['source']}), "
            f"{compiled} compiles counted"
        )
    finally:
        trainer.close()
        server.stop()


def learning_obs_self_test(n_steps: int = 6, eta: int = 4) -> str:
    """Short CPU RL run with FORCED staleness asserting the learning-health
    observatory (docs/observability.md "Learning-health observatory") with
    MEASURED numbers:

    - eta=4 lets the rollout pipeline pre-generate ~(eta+1)*bs trajectories
      at version 0; FIFO consumption then trains them at lags 0..eta, so
      several lag buckets fill without any mocking;
    - the highest populated lag bucket must show strictly higher windowed
      behave-|KL| than lag-0 (the decoupled-loss drift the staleness bound
      is supposed to keep corrigible), and a tight behave importance-weight
      cap must leave a non-zero cap-hit tail;
    - the trajectory lineage ring must join journal frames to train-step
      loss stats by trace id: generate -> journal -> consume -> update for
      the same task id, with per-trajectory clip fraction attributed.
    """
    import os
    import tempfile

    import jax
    import numpy as np

    from areal_tpu.api.config import (
        ChaosConfig,
        DatasetConfig,
        InferenceEngineConfig,
        MeshConfig,
        MicroBatchSpec,
        OptimizerConfig,
        PPOActorConfig,
        PPOConfig,
        RecoverConfig,
        SaverConfig,
        ServerConfig,
        StatsLoggerConfig,
    )
    from areal_tpu.api.io_struct import FinetuneSpec, GenerationHyperparameters
    from areal_tpu.autopilot.signals import labeled_total
    from areal_tpu.engine.train_engine import JaxTrainEngine
    from areal_tpu.infra.staleness_manager import LAG_BUCKET_LABELS
    from areal_tpu.inference.client import RemoteJaxEngine
    from areal_tpu.inference.decode_engine import DecodeEngine
    from areal_tpu.inference.server import ServerThread
    from areal_tpu.observability import lineage as lineage_mod
    from areal_tpu.observability.metrics import (
        get_registry,
        parse_prometheus_text,
    )
    from areal_tpu.robustness import FaultInjector
    from areal_tpu.trainer.rl_trainer import PPOTrainer
    from areal_tpu.workflow.rlvr import RLVRWorkflow

    root = tempfile.mkdtemp(prefix="areal_learning_obs_selftest_")
    tiny = tiny_model_config()
    actor_cfg = PPOActorConfig(
        init_from_scratch=True,
        dtype="float32",
        param_dtype="float32",
        mesh=MeshConfig(data=-1, fsdp=1, seq=1, model=1),
        # the lr IS the experiment: the policy must measurably move per
        # version so lag maps to drift (behave KL)
        optimizer=OptimizerConfig(lr=2e-2, lr_scheduler_type="constant"),
        mb_spec=MicroBatchSpec(max_tokens_per_mb=100_000),
        bucket_step=64,
        group_size=1,
        ppo_n_minibatches=1,
        adv_norm=None,
        kl_ctl=0.0,
        use_decoupled_loss=True,
        prox_logp_mode="recompute",
        # tight cap: a few drifted tokens must hit it (the tail-mass assert)
        behav_imp_weight_cap=1.01,
    )
    engine = JaxTrainEngine(actor_cfg, model_config=tiny)
    engine.initialize(FinetuneSpec(1, 16, 2))
    scfg = ServerConfig(
        max_batch_size=8,
        max_seq_len=128,
        decode_steps_per_call=4,
        seed=0,
        mesh=MeshConfig(data=-1, fsdp=1, seq=1, model=1),
    )
    dec = DecodeEngine(
        scfg, params=jax.tree.map(np.asarray, engine.params), model_cfg=tiny
    )
    dec.initialize()
    server = ServerThread(scfg, dec)
    server.start()
    rollout = RemoteJaxEngine(
        InferenceEngineConfig(
            # wide open concurrency + eta>0: the whole staleness budget
            # ((eta + v + 1) * bs accepted) pre-generates at version 0 and
            # drains FIFO over the next eta steps — lag 0..eta, measured
            max_concurrent_rollouts=16,
            consumer_batch_size=2,
            max_head_offpolicyness=eta,
            request_timeout=120,
        ),
        addresses=[server.address],
    )
    rollout.initialize()
    # seeded chaos: deterministic small stalls on the client POSTs — the
    # asserts below must hold under perturbed timing, not a quiet lab
    rollout.install_fault_injector(
        FaultInjector(
            ChaosConfig(enabled=True, seed=11, stall_prob=0.2, stall_s=0.01)
        )
    )
    cfg = PPOConfig(
        experiment_name="learning-obs",
        trial_name="t0",
        total_train_epochs=50,
        total_train_steps=n_steps,
        weight_update_mode="mem",
        # SAMPLED generation: greedy slots run at temp->0, whose sampling
        # distribution is deterministic and reports ~0 logprobs — no
        # behavior policy to be off of. RL rollouts sample; so does this.
        gconfig=GenerationHyperparameters(
            n_samples=1, max_new_tokens=4, greedy=False
        ),
        train_dataset=DatasetConfig(batch_size=2, shuffle=True),
        actor=actor_cfg,
        saver=SaverConfig(fileroot=root),
        checkpointer=SaverConfig(fileroot=root),
        recover=RecoverConfig(mode="disabled", fileroot=root),
        stats_logger=StatsLoggerConfig(fileroot=root),
    )
    cfg.evaluator.fileroot = root
    cfg.cluster.fileroot = root
    # the journal is part of the lineage chain under test
    cfg.rollout.journal.enabled = True
    cfg.rollout.journal.dir = os.path.join(root, "journal")
    cfg.rollout.journal.fsync = False
    rng = np.random.default_rng(0)
    dataset = [
        {"prompt_ids": rng.integers(2, 100, 3).tolist()} for _ in range(16)
    ]
    wf = RLVRWorkflow(
        lambda *a, **k: 1.0,
        GenerationHyperparameters(max_new_tokens=4, greedy=False),
    )

    def lag_counters() -> dict[str, dict[str, float]]:
        samples = parse_prometheus_text(get_registry().render_prometheus())
        out: dict[str, dict[str, float]] = {}
        for label in LAG_BUCKET_LABELS:
            out[label] = {
                "tokens": labeled_total(
                    samples, "areal_train_lag_tokens_total", lag_bucket=label
                )
                or 0.0,
                "kl": labeled_total(
                    samples,
                    "areal_train_lag_behave_kl_sum_total",
                    lag_bucket=label,
                )
                or 0.0,
                "capped": labeled_total(
                    samples, "areal_train_lag_capped_total", lag_bucket=label
                )
                or 0.0,
            }
        return out

    c0 = lag_counters()
    trainer = PPOTrainer(cfg, dataset, rollout=rollout, actor_engine=engine)
    try:
        trainer.train(workflow=wf)
        journal = trainer.journal
        if journal is None:
            raise AssertionError("trajectory journal was not attached")
        journal_tasks = {e.task_id for e in journal.scan()}
    finally:
        trainer.close()
        server.stop()
    c1 = lag_counters()
    delta = {
        label: {k: c1[label][k] - c0[label][k] for k in c0[label]}
        for label in LAG_BUCKET_LABELS
    }
    if delta["0"]["tokens"] <= 0:
        raise AssertionError(f"no lag-0 tokens trained: {delta}")
    high_label = next(
        (l for l in ("4+", "2", "1") if delta[l]["tokens"] > 0), None
    )
    if high_label is None:
        raise AssertionError(
            f"forced staleness produced no off-policy bucket: {delta} — "
            "every trained token was lag 0"
        )
    kl0 = delta["0"]["kl"] / delta["0"]["tokens"]
    klh = delta[high_label]["kl"] / delta[high_label]["tokens"]
    if not klh > kl0:
        raise AssertionError(
            f"no KL separation: lag-0 behave-|KL| {kl0:.5f} vs lag-"
            f"{high_label} {klh:.5f} — staleness is not being measured as "
            "drift"
        )
    capped = sum(d["capped"] for d in delta.values())
    if capped <= 0:
        raise AssertionError(
            f"behave cap {actor_cfg.behav_imp_weight_cap} left zero cap-hit "
            "tail mass — the dead-weight tail is not observed"
        )
    # lineage join: generate -> journal -> consume -> update by trace id
    ring = lineage_mod.get_lineage()
    joined = [
        r
        for r in ring.recent()
        if r.trained_version is not None and r.clip_fraction is not None
    ]
    if not joined:
        raise AssertionError("no lineage record joined to train-step stats")
    chained = [
        r
        for r in joined
        if r.journaled
        and r.consumed_version is not None
        and r.task_id in journal_tasks
    ]
    if not chained:
        raise AssertionError(
            "no lineage record closes the full chain (journaled + consumed "
            f"+ trained): {len(joined)} joined, journal has "
            f"{len(journal_tasks)} tasks"
        )
    lags = sorted(
        {r.lag_at_consume for r in chained if r.lag_at_consume is not None}
    )
    return (
        f"{len(joined)} trajectories joined generate->journal->consume->"
        f"update ({len(chained)} full-chain, consume lags {lags}); "
        f"behave-|KL| lag-0 {kl0:.4f} < lag-{high_label} {klh:.4f}; "
        f"cap-hit tail {capped:.0f} tokens "
        f"(cap {actor_cfg.behav_imp_weight_cap})"
    )


def preemption_self_test(kill_after_version: int = 1) -> str:
    """The whole spot-TPU lifecycle on CPU (docs/fault_tolerance.md):

    1. tiny 1-replica fleet + real PPOTrainer (journal on, recover
       freq_steps=1, async dumps);
    2. a REAL SIGTERM delivered to this process mid-step — the flag-only
       handler + step-loop polling must abort the step, emergency-dump,
       and return from train() cleanly (``trainer.preempted``);
    3. relaunch: a second trainer resumes one step after the dump and
       replays >= 1 journaled in-bound trajectory (re-generation saved);
    4. the replica drains under load: 429s on new admissions, in-flight
       work finished/parked, 0 leaked pages, 0 unterminated timelines;
    5. async-vs-sync checkpoint pause: the async path's step-loop pause
       must be <= 1/5 of the measured sync save time.
    """
    import os
    import signal
    import threading
    import time

    import jax
    import numpy as np

    from areal_tpu.api.config import (
        DatasetConfig,
        InferenceEngineConfig,
        MeshConfig,
        MicroBatchSpec,
        OptimizerConfig,
        PPOActorConfig,
        PPOConfig,
        PreemptionConfig,
        RecoverConfig,
        SaverConfig,
        ServerConfig,
        StatsLoggerConfig,
        TrajectoryJournalConfig,
    )
    from areal_tpu.api.io_struct import (
        FinetuneSpec,
        GenerationHyperparameters,
        ModelRequest,
        StepInfo,
    )
    from areal_tpu.engine.train_engine import JaxTrainEngine
    from areal_tpu.inference.client import RemoteJaxEngine
    from areal_tpu.inference.decode_engine import DecodeEngine
    from areal_tpu.inference.server import ServerThread
    from areal_tpu.models import qwen
    from areal_tpu.trainer.rl_trainer import PPOTrainer
    from areal_tpu.workflow.rlvr import RLVRWorkflow

    import tempfile

    root = tempfile.mkdtemp(prefix="areal_preempt_selftest_")
    tiny = tiny_model_config()

    def make_actor_cfg():
        return PPOActorConfig(
            init_from_scratch=True,
            dtype="float32",
            param_dtype="float32",
            mesh=MeshConfig(data=-1, fsdp=1, seq=1, model=1),
            optimizer=OptimizerConfig(lr=1e-3, lr_scheduler_type="constant"),
            mb_spec=MicroBatchSpec(max_tokens_per_mb=100_000),
            bucket_step=64,
            group_size=1,
            ppo_n_minibatches=1,
            adv_norm=None,
            kl_ctl=0.0,
            use_decoupled_loss=False,
            recompute_logprob=False,
        )

    def make_cfg(actor_cfg):
        cfg = PPOConfig(
            experiment_name="preempt",
            trial_name="t0",
            total_train_epochs=50,
            weight_update_mode="mem",
            gconfig=GenerationHyperparameters(
                n_samples=1, max_new_tokens=4, greedy=True
            ),
            train_dataset=DatasetConfig(batch_size=2, shuffle=True),
            actor=actor_cfg,
            saver=SaverConfig(fileroot=root),
            checkpointer=SaverConfig(fileroot=root),
            recover=RecoverConfig(mode="auto", freq_steps=1, fileroot=root),
            stats_logger=StatsLoggerConfig(fileroot=root),
        )
        cfg.evaluator.fileroot = root
        cfg.cluster.fileroot = root
        cfg.rollout = InferenceEngineConfig(
            max_concurrent_rollouts=4,
            consumer_batch_size=2,
            max_head_offpolicyness=4,
            request_timeout=120,
            journal=TrajectoryJournalConfig(enabled=True),
        )
        cfg.preemption = PreemptionConfig(grace_s=60.0)
        return cfg

    # -- fleet -------------------------------------------------------------
    engine = JaxTrainEngine(make_actor_cfg(), model_config=tiny)
    engine.initialize(FinetuneSpec(1, 16, 2))
    scfg = ServerConfig(
        max_batch_size=4,
        max_seq_len=128,
        decode_steps_per_call=4,
        seed=0,
        mesh=MeshConfig(data=-1, fsdp=1, seq=1, model=1),
    )
    dec = DecodeEngine(
        scfg, params=jax.tree.map(np.asarray, engine.params), model_cfg=tiny
    )
    dec.initialize()
    server = ServerThread(scfg, dec)
    server.start()
    rng = np.random.default_rng(0)
    dataset = [
        {"prompt_ids": rng.integers(2, 100, 3).tolist()} for _ in range(16)
    ]
    wf = RLVRWorkflow(
        lambda *a, **k: 1.0,
        GenerationHyperparameters(max_new_tokens=4, greedy=True),
    )

    def make_rollout():
        r = RemoteJaxEngine(
            make_cfg(make_actor_cfg()).rollout, addresses=[server.address]
        )
        r.initialize()
        return r

    rollout = make_rollout()
    cfg = make_cfg(make_actor_cfg())
    trainer = PPOTrainer(cfg, dataset, rollout=rollout, actor_engine=engine)

    # -- SIGTERM mid-step --------------------------------------------------
    def killer():
        deadline = time.time() + 180
        while time.time() < deadline:
            if rollout.get_version() >= kill_after_version:
                break
            time.sleep(0.05)
        time.sleep(0.2)  # land inside the NEXT step's rollout wait
        os.kill(os.getpid(), signal.SIGTERM)

    kt = threading.Thread(target=killer, daemon=True)
    kt.start()
    trainer.train(workflow=wf)
    kt.join(timeout=10)
    if not trainer.preempted:
        raise AssertionError("SIGTERM did not preempt the trainer")
    pair = trainer.recover_handler.read_recover_info()
    if pair is None:
        raise AssertionError("no loadable recover generation after preemption")
    info, _ = pair
    dumped_step = info.last_step_info.global_step
    journal_stats = trainer.journal.stats()
    trainer.close()

    # -- relaunch: resume + journal replay ---------------------------------
    engine2 = JaxTrainEngine(make_actor_cfg(), model_config=tiny)
    engine2.initialize(FinetuneSpec(1, 16, 2))
    rollout2 = make_rollout()
    trainer2 = PPOTrainer(
        make_cfg(make_actor_cfg()), dataset, rollout=rollout2, actor_engine=engine2
    )
    if trainer2.recover_info is None:
        raise AssertionError("relaunch did not load the recover checkpoint")
    resume_step = trainer2.recover_info.last_step_info.next().global_step
    if resume_step != dumped_step + 1:
        raise AssertionError(
            f"resume at step {resume_step}, expected {dumped_step + 1} "
            "(one recover interval)"
        )
    replayed = len(rollout2.executor._results)
    if replayed < 1:
        raise AssertionError(
            "relaunch replayed no journaled trajectories "
            f"(journal had {journal_stats['appended']} appended)"
        )

    # -- async-vs-sync checkpoint pause ------------------------------------
    sync_saver_dir = os.path.join(root, "pause_probe")
    from areal_tpu.utils.saver import Saver

    probe = Saver(
        SaverConfig(fileroot=sync_saver_dir, freq_steps=1), None, for_recover=True
    )
    t0 = time.monotonic()
    probe.save(engine2, 0, 0, 100)
    engine2.wait_for_save()
    sync_s = time.monotonic() - t0
    t0 = time.monotonic()
    probe.save_async(engine2, 0, 0, 101)
    async_pause_s = time.monotonic() - t0
    probe.wait_async()
    if async_pause_s * 5 > sync_s:
        raise AssertionError(
            f"async save pause {async_pause_s * 1e3:.1f}ms > 1/5 of sync "
            f"save {sync_s * 1e3:.1f}ms"
        )
    trainer2.close()

    # -- replica drain under load ------------------------------------------
    done: list = []
    for i in range(3):
        dec.submit(
            ModelRequest(
                input_ids=[3 + i, 7, 9],
                rid=f"drainload-{i}",
                gconfig=GenerationHyperparameters(
                    max_new_tokens=100_000, greedy=True, ignore_eos=True
                ),
            ),
            done.append,
        )
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if any(t is not None and t.out_tokens for t in dec._slot_task):
            break
        time.sleep(0.01)
    summary = dec.drain(budget_s=2.0)
    admit, reason, _ = dec.check_admission()
    if admit or reason != "draining":
        raise AssertionError(f"drained replica still admits ({reason!r})")
    if len(done) != 3:
        raise AssertionError(
            f"{3 - len(done)} in-flight requests left without a terminal"
        )
    if summary["leaked_pages"] != 0:
        raise AssertionError(f"{summary['leaked_pages']} KV pages leaked")
    if summary["unterminated_timelines"] != 0:
        raise AssertionError(
            f"{summary['unterminated_timelines']} unterminated timelines"
        )
    server.stop()
    return (
        f"SIGTERM mid-step -> emergency dump @ step {dumped_step}, resume @ "
        f"{resume_step}, {replayed} journaled trajectories replayed "
        f"(re-generation saved), drain {summary['drain_seconds']:.2f}s "
        f"(parked {summary['parked']}, 0 leaks), ckpt pause sync "
        f"{sync_s * 1e3:.0f}ms vs async {async_pause_s * 1e3:.0f}ms"
    )


def routing_self_test(
    n_replicas: int = 3, n_sessions: int = 6, turns: int = 3, seed: int = 17
) -> str:
    """Cache-aware routing brain end to end (docs/serving.md "Cache-aware
    routing"): a 3-replica fleet under seeded chaos stalls serves an
    80%-shared-prefix multi-turn workload through BOTH policies.

    Asserts: (1) cache-aware yields measurably more warm suffix-only
    prefill (radix hit tokens) than round-robin on the identical workload;
    (2) router decisions are audited into the flight ring with reasons;
    (3) an evicted replica receives ZERO routes while down, and after the
    respawn/rejoin its shadow prefix index reads cold."""
    import asyncio

    import jax

    from areal_tpu.api.config import (
        ChaosConfig,
        FaultToleranceConfig,
        InferenceEngineConfig,
        MeshConfig,
        RoutingConfig,
        ServerConfig,
    )
    from areal_tpu.api.io_struct import GenerationHyperparameters, ModelRequest
    from areal_tpu.inference.client import RemoteJaxEngine, close_loop_sessions
    from areal_tpu.inference.decode_engine import DecodeEngine
    from areal_tpu.inference.server import ServerThread
    from areal_tpu.models import qwen
    from areal_tpu.observability import timeline as tl_mod
    from areal_tpu.robustness import FaultInjector

    tiny = tiny_model_config()
    params = qwen.init_params(jax.random.PRNGKey(0), tiny)
    servers = []
    clients = []
    psz = 16
    try:
        for i in range(n_replicas):
            cfg = ServerConfig(
                max_batch_size=4,
                max_seq_len=256,
                decode_steps_per_call=4,
                page_size=psz,
                seed=i,
                mesh=MeshConfig(data=-1, fsdp=1, seq=1, model=1),
            )
            eng = DecodeEngine(cfg, params=params, model_cfg=tiny)
            eng.initialize()
            st = ServerThread(cfg, eng)
            st.start()
            servers.append(st)
        addrs = [s.address for s in servers]

        def make_client(policy: str) -> RemoteJaxEngine:
            c = RemoteJaxEngine(
                InferenceEngineConfig(
                    max_concurrent_rollouts=8,
                    consumer_batch_size=2,
                    max_head_offpolicyness=100,
                    request_timeout=60,
                    request_retries=5,
                    routing_policy=policy,
                    routing=RoutingConfig(
                        poll_interval_s=0.25, shadow_page_size=psz
                    ),
                    fault_tolerance=FaultToleranceConfig(
                        backoff_base_s=0.05,
                        backoff_max_s=0.5,
                        probe_interval_s=60.0,
                    ),
                ),
                addresses=list(addrs),
            )
            c.initialize()
            c.install_fault_injector(
                FaultInjector(
                    ChaosConfig(
                        enabled=True,
                        seed=seed,
                        stall_prob=0.2,
                        stall_s=0.05,
                        path_prefix="/generate",
                    )
                )
            )
            clients.append(c)
            return c

        g = GenerationHyperparameters(max_new_tokens=8, greedy=True)

        async def drive(client: RemoteJaxEngine, tag: str) -> None:
            # multi-turn sessions: each turn's prompt extends the previous
            # sequence — the conversation-history prefix structure the
            # router exploits. 80%+ of every turn-2+ prompt is shared
            # with state one replica already holds.
            async def session(s: int) -> None:
                base = [2 + (s % 40), 5] + [
                    3 + ((s * 7 + j) % 90) for j in range(62)
                ]
                ids = list(base)
                for t in range(turns):
                    req = ModelRequest(
                        input_ids=ids,
                        rid=f"{tag}-s{s}-t{t}",
                        gconfig=g,
                    )
                    resp = await client.agenerate(req)
                    ids = ids + list(resp.output_tokens) + [9 + t, 11, 13]
            await asyncio.gather(*[session(s) for s in range(n_sessions)])
            await close_loop_sessions()

        def fleet_stats() -> tuple[int, int]:
            hit = sum(s.engine.stats["prefix_hit_tokens"] for s in servers)
            pf = sum(s.engine.stats["prefill_tokens"] for s in servers)
            return hit, pf

        # --- arm 1: round robin -------------------------------------------
        rr = make_client("round_robin")
        asyncio.run(drive(rr, "rr"))
        rr_hit, rr_pf = fleet_stats()
        # flush every radix tree so the cache-aware arm starts as cold as
        # the round-robin arm did
        for s in servers:
            s.engine.flush_prefix_cache()
        # --- arm 2: cache aware -------------------------------------------
        ca = make_client("cache_aware")
        ca.router.poller.poll_once()  # live snapshots before first choice
        asyncio.run(drive(ca, "ca"))
        ca_hit, ca_pf = fleet_stats()
        ca_hit, ca_pf = ca_hit - rr_hit, ca_pf - rr_pf
        if ca_hit <= rr_hit:
            raise AssertionError(
                f"cache-aware warm prefill did not improve: hit tokens "
                f"{ca_hit} (cache_aware) vs {rr_hit} (round_robin)"
            )
        st = ca.router.stats()
        if st["decisions"].get("prefix_overlap", 0) == 0:
            raise AssertionError(
                f"no prefix_overlap decisions recorded: {st['decisions']}"
            )
        # decisions must be auditable in the flight ring
        ring = tl_mod.get_flight_recorder().snapshot()["events"]
        router_events = [e for e in ring if e.get("kind") == "router_decision"]
        if not router_events:
            raise AssertionError("no router_decision events in flight ring")
        if not all(
            (e.get("data") or {}).get("reason") for e in router_events[-10:]
        ):
            raise AssertionError("router_decision events missing reasons")
        # --- evict -> zero routes while down -> cold after respawn --------
        victim = addrs[0]
        ca.fleet.evict(victim)  # PR 3 supervision's administrative eviction
        routed = {
            ca.choose_server(req=ModelRequest(input_ids=[2, 3, 4 + i], gconfig=g))
            for i in range(24)
        }
        if victim in routed:
            raise AssertionError(f"evicted replica {victim} was routed to")
        # respawn/rejoin: the probe path closes the circuit and resets the
        # replica's router state (its radix tree restarted empty)
        ca.probe_fleet()
        if ca.fleet.state(victim) == "open":
            raise AssertionError("probe did not rejoin the healthy replica")
        if ca.router.shadow.pages_for(victim) != 0:
            raise AssertionError(
                "rejoined replica's shadow index was not reset to cold"
            )
        routed_after = {
            ca.choose_server(req=ModelRequest(input_ids=[2, 3, 4 + i], gconfig=g))
            for i in range(24)
        }
        if victim not in routed_after:
            raise AssertionError("rejoined replica never selected again")
        return (
            f"{n_sessions}x{turns}-turn sessions over {n_replicas} replicas "
            f"under chaos: warm hit tokens {rr_hit} (rr) -> {ca_hit} "
            f"(cache-aware), suffix prefill {rr_pf} -> {ca_pf}, "
            f"{len(router_events)} audited decisions, evicted replica got "
            f"0/24 routes while down and rejoined cold"
        )
    finally:
        for c in clients:
            c.destroy()
        for s in servers:
            s.stop()


def autopilot_self_test(
    window_s: float = 6.0,
    n_interactive: int = 8,
    n_rollout: int = 24,
    seed: int = 23,
) -> str:
    """Goodput autopilot end to end (docs/autopilot.md): one replica
    behind a 4-slot gateway, driven at ~2x capacity by a rollout flood
    under seeded chaos stalls, with the admission controller live.

    Asserts: (1) interactive traffic sheds under the static headroom=0
    start; (2) the controller WIDENS the interactive headroom in response
    (setpoint > 0, applied to the live gateway); (3) the interactive shed
    count drops in the second measured window; (4) every setpoint change
    is auditable in the flight ring (kind=autopilot_decision with
    controller/knob/old/new/reason). All measured on CPU."""
    import asyncio

    from areal_tpu.observability import timeline as tl_mod
    from areal_tpu.tools.bench_gateway import (
        LocalFleet,
        bench_autopilot_config,
        drive_gateway,
    )

    ap_cfg = bench_autopilot_config(interval_s=0.3)
    # the widening direction is the subject here; park the narrowing
    # clock so a quiet stretch inside the short window can't retract the
    # headroom mid-measurement (production narrows over minutes)
    ap_cfg.admission.narrow_after_quiet_rounds = 10_000
    fleet = LocalFleet(
        n_replicas=1,
        max_batch_size=1,
        chaos_stall_prob=0.5,
        chaos_stall_s=0.4,
        max_queue_depth=32,
        gateway_max_inflight=4,
        gateway_interactive_headroom=0,
        seed=seed,
        autopilot_cfg=ap_cfg,
    )
    ring = tl_mod.get_flight_recorder()
    seq0 = max(
        (e.get("seq", 0) for e in ring.snapshot()["events"]), default=0
    )

    async def run() -> tuple[list[int], int]:
        gateway_url, admin_key = await fleet.astart()
        try:
            sheds = []
            for _ in range(2):
                before = fleet.gw_state.shed["interactive"]
                await drive_gateway(
                    gateway_url,
                    admin_key,
                    n_interactive=n_interactive,
                    n_rollout=n_rollout,
                    duration_s=window_s,
                    interactive_tokens=8,
                    rollout_tokens=128,
                    interactive_deadline_s=window_s * 3,
                    rollout_deadline_s=window_s * 3,
                )
                sheds.append(fleet.gw_state.shed["interactive"] - before)
            return sheds, fleet.gw_state.interactive_headroom
        finally:
            await fleet.astop()

    sheds, headroom = asyncio.run(run())
    if sheds[0] == 0:
        raise AssertionError(
            "interactive traffic never shed under headroom=0 — the "
            "scenario was not a 2x overload"
        )
    if headroom <= 0:
        raise AssertionError(
            "admission controller never widened the interactive headroom"
        )
    if sheds[1] >= sheds[0]:
        raise AssertionError(
            f"interactive shed count did not drop after the controller "
            f"widened headroom: {sheds[0]} -> {sheds[1]}"
        )
    evs = [
        e
        for e in ring.snapshot()["events"]
        if e.get("kind") == "autopilot_decision" and e.get("seq", 0) > seq0
    ]
    if not evs:
        raise AssertionError("no autopilot_decision events in flight ring")
    widen = [
        e
        for e in evs
        if (e.get("data") or {}).get("knob") == "gateway_interactive_headroom"
        and (e.get("data") or {}).get("reason") == "interactive_shed"
    ]
    if not widen:
        raise AssertionError(
            "no audited interactive_shed headroom decision in flight ring"
        )
    if not all(
        {"controller", "knob", "old", "new", "reason"}
        <= set(e.get("data") or {})
        for e in evs
    ):
        raise AssertionError("autopilot_decision events missing audit fields")
    return (
        f"{n_interactive}+{n_rollout} clients @~2x through a 4-slot "
        f"gateway: interactive sheds {sheds[0]} -> {sheds[1]} after the "
        f"controller widened headroom 0 -> {headroom}; "
        f"{len(evs)} audited decisions in the flight ring"
    )


def spec_decode_self_test() -> str:
    """Speculative decoding end to end (docs/serving.md "Speculative
    decoding"): a spec-enabled tiny engine over an acceptance-friendly
    repetitive workload.

    Asserts: (1) speculation genuinely ran — rounds > 0 and acceptance
    rate > 0 (prompt-lookup drafts of a periodic prompt must land);
    (2) zero leaked KV pages after settling (free + radix-held == pool
    total: rejected tails were rolled back through the refcounted pool);
    (3) request timelines carry the draft/verify stages and the kernel
    probe's per-step exact-sum identity holds with the two new phases
    in the vocabulary."""
    import threading
    import time

    import jax

    from areal_tpu.api.config import MeshConfig, ServerConfig, SpeculativeConfig
    from areal_tpu.api.io_struct import GenerationHyperparameters, ModelRequest
    from areal_tpu.inference.decode_engine import DecodeEngine
    from areal_tpu.models import qwen
    from areal_tpu.observability.kernel_probe import DECODE_PHASES

    tiny = tiny_model_config()
    params = qwen.init_params(jax.random.PRNGKey(0), tiny)
    cfg = ServerConfig(
        max_batch_size=2,
        max_seq_len=256,
        decode_steps_per_call=4,
        page_size=16,
        seed=0,
        mesh=MeshConfig(data=-1, fsdp=1, seq=1, model=1),
        speculative=SpeculativeConfig(enabled=True, drafter="tree"),
    )
    eng = DecodeEngine(cfg, params=params, model_cfg=tiny)
    eng.initialize()
    eng.start()
    try:
        done = threading.Event()
        got: list = []
        lock = threading.Lock()

        def cb(resp):
            with lock:
                got.append(resp)
                if len(got) == 3:
                    done.set()

        for i in range(3):
            eng.submit(
                ModelRequest(
                    # periodic prompts: prompt-lookup drafting proposes the
                    # continuation the model itself settles into
                    input_ids=[7 + i, 3, 9] * 8,
                    gconfig=GenerationHyperparameters(
                        max_new_tokens=32, greedy=True
                    ),
                ),
                cb,
            )
        assert done.wait(timeout=300.0), f"only {len(got)}/3 finished"
        rounds = eng.stats["spec_rounds"]
        drafted = eng.stats["spec_draft_tokens"]
        accepted = eng.stats["spec_accepted_tokens"]
        assert rounds > 0, "speculation never ran"
        assert drafted > 0 and accepted > 0, (
            f"acceptance rate must be > 0 on a repetitive prompt "
            f"(drafted {drafted}, accepted {accepted})"
        )
        # settle, then the allocator audit: every page free or radix-held
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            snap = eng.admission_snapshot()
            if snap["queue_depth"] == 0 and snap["active_slots"] == 0:
                break
            time.sleep(0.05)
        held = eng.prefix_cache_stats()["pages_held"] if eng.slots.radix is not None else 0
        leaked = eng.slots.pool.used - held
        assert leaked == 0, f"{leaked} leaked KV pages after settling"
        # timeline stage coverage: the spec rounds marked draft + verify
        staged = set()
        for rec in eng.timeline.recent():
            staged |= {ev["stage"] for ev in rec["events"]}
        for want in ("draft", "verify"):
            assert want in staged, (
                f"timeline missing the {want} stage (saw {sorted(staged)})"
            )
        # kernel-probe exact-sum identity over the widened phase vocabulary
        recs = eng.kprobe.recent()
        assert recs, "no decode steps recorded by the kernel probe"
        worst = 0.0
        spec_phase_s = 0.0
        for rec in recs:
            bd = rec["breakdown"]
            named = sum(bd[f"{p}_s"] for p in DECODE_PHASES)
            worst = max(worst, abs(named + bd["other_s"] - bd["total_s"]))
            spec_phase_s += bd["draft_s"] + bd["verify_s"]
        assert worst < 1e-9, f"phase-sum identity violated by {worst:.3e}s"
        assert spec_phase_s > 0, "draft/verify phases recorded no time"
    finally:
        eng.stop()
    return (
        f"acceptance {accepted}/{drafted} "
        f"({accepted / drafted:.0%}) over {rounds} rounds, 0 leaked pages, "
        f"draft+verify staged, identity residual {worst:.1e}s"
    )


def gateway_tier_self_test(
    n_replicas: int = 2,
    n_shards: int = 3,
    n_interactive: int = 9,
    n_rollout: int = 15,
    duration_s: float = 2.0,
    seed: int = 31,
) -> str:
    """Horizontally-sharded gateway tier end to end (docs/serving.md
    "Gateway tier"): 3 shards over a 2-replica fleet, sessions placed by
    the consistent-hash tier client, with seeded chaos arming a
    mid-run shard kill.

    Asserts: (1) the kill actually fired and the membership view
    converged to the survivors; (2) zero responseless requests — every
    session completed or ended on a real terminal status, and with no
    backpressure in this fleet that means completed == sent; (3) the
    survivors absorbed the re-hashed load: clients observed failovers,
    and the keyspace the victim owned was served by surviving shards."""
    import asyncio
    import time

    from areal_tpu.api.config import ChaosConfig
    from areal_tpu.robustness import FaultInjector
    from areal_tpu.tools.bench_gateway import (
        LocalFleet,
        _TierResolver,
        drive_gateway,
    )

    async def run() -> str:
        fleet = LocalFleet(
            n_replicas=n_replicas,
            n_gateways=n_shards,
            chaos_stall_prob=0.0,
            seed=seed,
        )
        await fleet.astart()
        try:
            assert fleet.tier is not None
            assert len(fleet.tier.addresses()) == n_shards
            resolver = _TierResolver(fleet.tier)
            # seeded chaos, restricted to ONE victim shard: the injector
            # fires each registered target at most once, so "kill one
            # shard mid-run" is a harness invariant, not a probability
            victim = sorted(fleet.tier.shards)[-1]
            injector = FaultInjector(
                ChaosConfig(
                    enabled=True,
                    seed=seed,
                    gateway_kill_prob=0.35,
                    path_prefix="/generate",
                )
            )
            injector.set_gateway_kill_targets(
                {victim: fleet.tier.kill_callables()[victim]}
            )
            fleet.client.install_fault_injector(injector)
            report = await drive_gateway(
                fleet.gateway_url,
                fleet.admin_key,
                n_interactive=n_interactive,
                n_rollout=n_rollout,
                duration_s=duration_s,
                interactive_deadline_s=30.0,
                rollout_deadline_s=30.0,
                interactive_tokens=8,
                rollout_tokens=16,
                turns=2,
                greedy=True,
                resolver=resolver,
            )
            tot = report["totals"]
            kills = injector.stats().get("gw_kill", 0)
            assert kills == 1, f"chaos never killed the shard ({kills=})"
            # zero responseless requests: every session reached a real
            # terminal (here: completion — this fleet has no admission
            # limit and generous deadlines, so shed/reaped would itself
            # be a tier failure)
            assert tot["errors"] == 0, f"responseless requests: {tot}"
            assert tot["completed"] == tot["sent"], (
                f"sessions lost mid-failover: {tot}"
            )
            # the survivors absorbed the re-hashed load: clients hit the
            # dead shard, failed over, and the victim's keyspace was
            # served by surviving shards
            assert resolver.failovers > 0, (
                "no client ever failed over — kill happened outside the "
                "measured run?"
            )
            survivors = {
                sid: tok
                for sid, tok in resolver.shard_tokens.items()
                if sid != victim
            }
            assert sum(survivors.values()) > 0, (
                f"survivors served nothing: {resolver.shard_tokens}"
            )
            # membership converges: the victim's record expires from the
            # name_resolve view (abandoned keepalive -> TTL), leaving
            # exactly the survivors serving
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                if len(fleet.tier.directory.view()) == n_shards - 1:
                    break
                await asyncio.sleep(0.2)
            view = fleet.tier.directory.view()
            assert len(view) == n_shards - 1, (
                f"membership never converged: {sorted(view)}"
            )
            assert victim not in view, f"dead shard still in view: {victim}"
            return (
                f"{tot['completed']}/{tot['sent']} sessions completed over "
                f"{n_shards} shards with shard {victim} killed mid-run: "
                f"0 responseless, {resolver.failovers} failovers, "
                f"survivor tokens {sorted(survivors.items())}, membership "
                f"converged to {len(view)} shards"
            )
        finally:
            await fleet.astop()

    return asyncio.run(run())


if __name__ == "__main__":
    raise SystemExit(main())
