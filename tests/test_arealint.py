"""arealint unit tests: every rule family against its good/bad fixture pair,
suppression comments, baseline matching, finding ordering, CLI contract
(ISSUE 2: static-analysis suite)."""

import json
from pathlib import Path

import pytest

from areal_tpu.analysis import Analyzer, run_analysis
from areal_tpu.analysis.core import (
    SourceFile,
    load_baseline,
    render_baseline,
)
from areal_tpu.tools import arealint as cli

FIXTURES = Path(__file__).parent / "analysis_fixtures"


def rules_in(path: Path, rule_filter=None) -> list[str]:
    res = run_analysis([path], rules=rule_filter, baseline_path=None)
    return [f.rule for f in res.findings]


# ---------------------------------------------------------------------------
# rule families: true positives on *_bad.py, silence on *_good.py
# ---------------------------------------------------------------------------


def test_asy_bad_fixture():
    rules = rules_in(FIXTURES / "asy_bad.py", ["ASY"])
    assert "ASY001" in rules  # time.sleep in async
    assert "ASY002" in rules  # sync HTTP in async
    assert "ASY003" in rules  # blocking lock in async
    assert rules.count("ASY004") >= 2  # self-method and module helper hops


def test_asy_good_fixture():
    assert rules_in(FIXTURES / "asy_good.py", ["ASY"]) == []


def test_jax_bad_fixture():
    rules = rules_in(FIXTURES / "jax_bad.py", ["JAX"])
    assert "JAX001" in rules  # print under @jax.jit
    assert rules.count("JAX002") >= 3  # np.random, time.time, random.random
    assert "JAX003" in rules  # self mutation inside lax.scan body
    assert "JAX004" in rules  # set iteration
    assert "JAX005" in rules  # getattr through the alias hop


def test_jax_good_fixture():
    assert rules_in(FIXTURES / "jax_good.py", ["JAX"]) == []


def test_thr_bad_fixture():
    res = run_analysis([FIXTURES / "thr_bad.py"], rules=["THR"], baseline_path=None)
    attrs = {f.key.rsplit(":", 1)[1] for f in res.findings}
    # direct loop write, transitive helper write, local-def thread target
    assert {"counter", "last_error", "ready"} <= attrs


def test_thr_good_fixture():
    assert rules_in(FIXTURES / "thr_good.py", ["THR"]) == []


def test_cfg_bad_fixture():
    res = run_analysis([FIXTURES / "cfg_bad.py"], rules=["CFG"], baseline_path=None)
    by_rule = {}
    for f in res.findings:
        by_rule.setdefault(f.rule, []).append(f.message)
    assert any("max_concurent_rollouts" in m for m in by_rule["CFG001"])
    assert any("freq_minutes" in m for m in by_rule["CFG001"])  # nested chain
    assert any("consumer_batchsize" in m for m in by_rule["CFG001"])  # self cap
    assert any("max_batchsize" in m for m in by_rule["CFG002"])
    assert any("page_sizes" in m for m in by_rule["CFG003"])


def test_cfg_good_fixture():
    assert rules_in(FIXTURES / "cfg_good.py", ["CFG"]) == []


def test_obs_bad_fixture():
    rules = rules_in(FIXTURES / "obs_bad.py", ["OBS"])
    # two registrations outside the catalog: the counter AND the
    # trainer-observatory phase histogram (histogram() is a registration
    # method too — a rogue phase panel must not slip past the gate)
    assert rules.count("OBS001") == 2
    assert rules.count("OBS002") == 2  # two misspelled references


def test_obs_good_fixture():
    assert rules_in(FIXTURES / "obs_good.py", ["OBS"]) == []


def test_exc_bad_fixture():
    res = run_analysis([FIXTURES / "exc_bad.py"], rules=["EXC"], baseline_path=None)
    assert len(res.findings) == 6, [f.render() for f in res.findings]
    assert all(f.rule == "EXC001" for f in res.findings)
    tokens = {f.key.rsplit(":", 1)[1] for f in res.findings}
    # network, file, repo transport helpers, os file ops all recognized
    assert "urllib.request.urlopen" in tokens
    assert "http_json" in tokens
    assert "self._post_json" in tokens
    assert "os.replace" in tokens


def test_exc_good_fixture():
    assert rules_in(FIXTURES / "exc_good.py", ["EXC"]) == []


def test_sig_bad_fixture():
    rules = rules_in(FIXTURES / "sig_bad.py", ["SIG"])
    # direct blocking call, one-hop helper reach, print in a self.method
    # handler resolved through its Attribute registration
    assert rules.count("SIG001") >= 4
    assert rules.count("SIG002") >= 2  # with-lock + .acquire()
    assert rules.count("SIG003") >= 2  # Thread ctor + comprehension


def test_sig_bad_reaches_helpers_and_methods():
    res = run_analysis([FIXTURES / "sig_bad.py"], rules=["SIG"], baseline_path=None)
    msgs = [f.message for f in res.findings]
    assert any("reached from handler 'handler_blocks'" in m for m in msgs)
    assert any("`print`" in m for m in msgs)  # self._on_term method handler


def test_sig_good_fixture():
    # flag-only handlers, pre-armed drainer threads, and unregistered
    # functions that block freely: all silent
    assert rules_in(FIXTURES / "sig_good.py", ["SIG"]) == []


def test_prf_bad_fixture():
    res = run_analysis([FIXTURES / "prf_bad.py"], rules=["PRF"], baseline_path=None)
    rules = [f.rule for f in res.findings]
    assert "PRF001" in rules  # block_until_ready in hot fn
    assert "PRF002" in rules  # np.asarray of a device value
    assert rules.count("PRF003") >= 2  # float() in loop + .item() in marked fn
    msgs = [f.message for f in res.findings]
    # one-hop reachability names the seed that made the helper hot
    assert any("reachable from hot `Engine._loop`" in m for m in msgs)
    # the marker comment seeds hotness without a conventional name
    assert any("marked_poller" in m for m in msgs)


def test_prf_good_fixture():
    assert rules_in(FIXTURES / "prf_good.py", ["PRF"]) == []


def test_prf_cold_path_never_fires():
    """The reachability negative: `initialize` holds the same sync calls
    as the hot loop and must stay silent — hotness is a call-graph fact,
    not a per-call pattern."""
    res = run_analysis([FIXTURES / "prf_bad.py"], rules=["PRF"], baseline_path=None)
    assert all("initialize" not in f.key for f in res.findings)
    assert all("initialize" not in f.message for f in res.findings)


def test_prf_hot_marker_in_new_file(tmp_path):
    # a sync is only a finding when reachable from a seed; the marker
    # makes an arbitrarily-named function a seed
    src = tmp_path / "mod.py"
    src.write_text(
        "import jax\n"
        "def quiet(x):\n"
        "    return jax.device_get(x)\n"
    )
    assert rules_in(src, ["PRF"]) == []
    src.write_text(
        "import jax\n"
        "# arealint: hot-path\n"
        "def loud(x):\n"
        "    return jax.device_get(x)\n"
    )
    assert rules_in(src, ["PRF"]) == ["PRF001"]


def test_don_bad_fixture():
    res = run_analysis([FIXTURES / "don_bad.py"], rules=["DON"], baseline_path=None)
    by_rule = {}
    for f in res.findings:
        by_rule.setdefault(f.rule, []).append(f)
    tokens = {f.key.rsplit(":", 1)[1] for f in by_rule["DON001"]}
    assert {"params", "opt_state"} <= tokens  # both un-donated step args
    assert len(by_rule["DON002"]) == 1  # self.params read after donation
    assert "self.params" in by_rule["DON002"][0].message


def test_don_good_fixture():
    assert rules_in(FIXTURES / "don_good.py", ["DON"]) == []


def test_don002_opposite_branch_is_not_use_after(tmp_path):
    """A read in the OTHER branch of the donating if never executes on
    the donation path — must not fire (branch-aware scan); a read on the
    shared path after the if still must."""
    src = tmp_path / "mod.py"
    src.write_text(
        "import jax\n"
        "step = jax.jit(lambda state: state, donate_argnums=(0,))\n"
        "def run(self, fast):\n"
        "    if fast:\n"
        "        tmp = step(self.state)\n"
        "    else:\n"
        "        tmp = len(self.state)\n"  # exclusive branch: fine
        "    return tmp\n"
    )
    assert rules_in(src, ["DON"]) == []
    src.write_text(
        "import jax\n"
        "step = jax.jit(lambda state: state, donate_argnums=(0,))\n"
        "def run(self, fast):\n"
        "    if fast:\n"
        "        tmp = step(self.state)\n"
        "    return len(self.state)\n"  # shared path: dead on fast=True
    )
    assert rules_in(src, ["DON"]) == ["DON002"]


def test_shd_bad_fixture():
    rules = rules_in(FIXTURES / "shd_bad.py", ["SHD"])
    assert sorted(rules) == ["SHD001", "SHD002", "SHD003"]


def test_shd_good_fixture():
    # includes a locally-declared Mesh axis ('stage') and a spec-shaped
    # helper name that must not be mistaken for PartitionSpec
    assert rules_in(FIXTURES / "shd_good.py", ["SHD"]) == []


def test_rcp_bad_fixture():
    rules = rules_in(FIXTURES / "rcp_bad.py", ["RCP"])
    assert sorted(rules) == ["RCP001", "RCP002", "RCP003"]


def test_rcp_good_fixture():
    # the keyed fn-cache guard idiom and stable-key pytrees stay silent
    assert rules_in(FIXTURES / "rcp_good.py", ["RCP"]) == []


def test_wire_bad_fixture():
    """The bad fixture is a self-contained client+server pair drifted in
    every WIRE way: each rule in the family fires at least once."""
    rules = rules_in(FIXTURES / "wire_bad.py", ["WIRE"])
    assert {"WIRE001", "WIRE002", "WIRE003", "WIRE004", "WIRE005"} == set(rules)
    # WIRE002 fires twice: unread key sent AND required key omitted
    assert rules.count("WIRE002") == 2


def test_wire_good_fixture():
    # same server, a contract-faithful client, headers via api/wire.py
    assert rules_in(FIXTURES / "wire_good.py", ["WIRE"]) == []


def test_lck_bad_fixture():
    rules = rules_in(FIXTURES / "lck_bad.py", ["LCK"])
    assert {"LCK001", "LCK002", "LCK003", "LCK004"} == set(rules)


def test_lck_good_fixture():
    # consistent order, while-predicate wait, RPC outside the lock, and
    # every event flip under its owning lock stay silent
    assert rules_in(FIXTURES / "lck_good.py", ["LCK"]) == []


def test_krn_bad_fixture():
    """Two launches wearing every kernel-safety defect: a plain
    pallas_call (all five rules) and a PrefetchScalarGridSpec launch with
    scalar-prefetch operand drift + no interpret plumb-through — the
    defect shape of the suffix-attention kernel family."""
    rules = rules_in(FIXTURES / "krn_bad.py", ["KRN"])
    assert {"KRN001", "KRN002", "KRN003", "KRN004", "KRN005"} == set(rules)
    # the prefetch launch fires its own KRN002 (2 prefetch + 1 in + 1 out
    # + 1 scratch = 5 supplied, 4 taken) and its own KRN005
    assert rules.count("KRN002") == 2
    assert rules.count("KRN005") == 2


def test_krn_good_fixture():
    # matched index-map arity, operand plan (incl. scalar-prefetch refs),
    # no input writes, exact grid, interpret= exposed on both launches
    assert rules_in(FIXTURES / "krn_good.py", ["KRN"]) == []


def test_pvt_bad_fixture():
    """Unguarded private import, drifted pin, and vanished pin target —
    all REPORTED findings, none a crash (the analyzer resolves the pins
    against the really-installed jax)."""
    res = run_analysis(
        [FIXTURES / "pvt_bad.py"], rules=["PVT"], baseline_path=None
    )
    assert {"PVT001", "PVT002", "PVT003"} == {f.rule for f in res.findings}
    drift = next(f for f in res.findings if f.rule == "PVT002")
    # the finding carries the parameter diff, naming a really-removed pin
    # entry and a really-present installed parameter
    assert "a_param_jax_renamed" in drift.message
    assert "init_flag_ref" in drift.message


def test_pvt_good_fixture():
    # gated import, inline inspect.signature pin matching the installed
    # jax, and the pin_signature helper idiom all stay silent
    assert rules_in(FIXTURES / "pvt_good.py", ["PVT"]) == []


def test_flash_kernels_are_the_repos_own_and_need_no_pin():
    """The training flash kernels are a fork in ``ops/flash_kernels.py``
    (ISSUE 33): neither it nor ``ops/attention.py`` imports a private jax
    module any more, so there is no pin for PVT002 to re-check, and the
    fork's three launches pass the KRN geometry rules (index-map arity with
    the scalar-prefetch refs, ``interpret=`` exposed)."""
    from areal_tpu.ops import attention, flash_kernels

    for mod in (attention, flash_kernels):
        src = Path(mod.__file__)
        assert rules_in(src, ["PVT", "KRN"]) == []
        assert "pallas.ops" not in src.read_text() and "_EXPECTED_" not in src.read_text()
    assert not hasattr(attention, "pinned_block_sizes")
    blocks = attention.flash_block_sizes(attention.FlashTiles(512, 256, 128))
    assert blocks == flash_kernels.FlashBlocks((512, 512), (256, 256), (128, 128))
    with pytest.raises(ValueError, match="tile edge"):
        flash_kernels._check_blocks("forward", 1024, 384, 128)


def test_msh_bad_fixture():
    rules = rules_in(FIXTURES / "msh_bad.py", ["MSH"])
    assert {"MSH001", "MSH002", "MSH003"} == set(rules)


def test_msh_good_fixture():
    # declared axes, pmap-bound local axis, matching out_specs, and the
    # jax_compat-routed constraint stay silent
    assert rules_in(FIXTURES / "msh_good.py", ["MSH"]) == []


def test_wire_response_var_rebinding_unions_not_narrows(tmp_path):
    """A handler that returns a response var, rebinds it, and returns it
    again emits the UNION of both literals — a consumer reading a key
    from the first binding must not fire a false WIRE003."""
    src = tmp_path / "mod.py"
    src.write_text(
        "from aiohttp import web\n"
        "class S:\n"
        "    def build(self):\n"
        "        app = web.Application()\n"
        "        app.add_routes([web.post('/q', self.h)])\n"
        "        return app\n"
        "    async def h(self, request):\n"
        "        out = {'cached': True}\n"
        "        if request.query.get('hit'):\n"
        "            return web.json_response(out)\n"
        "        out = {'status': 'ok'}\n"
        "        return web.json_response(out)\n"
        "class C:\n"
        "    async def _post_json(self, addr, path, payload):\n"
        "        return {}\n"
        "    async def go(self, addr):\n"
        "        d = await self._post_json(addr, '/q', {})\n"
        "        return d.get('cached'), d.get('status')\n"
    )
    assert rules_in(src, ["WIRE"]) == []


def test_wire_body_var_resolves_to_binding_before_call(tmp_path):
    """A body variable rebound AFTER a call must not retroactively change
    what that call sent (was a false WIRE002: last-binding-wins)."""
    src = tmp_path / "mod.py"
    src.write_text(
        "from aiohttp import web\n"
        "class S:\n"
        "    def build(self):\n"
        "        app = web.Application()\n"
        "        app.add_routes([web.post('/p', self.hp),\n"
        "                        web.post('/q', self.hq)])\n"
        "        return app\n"
        "    async def hp(self, request):\n"
        "        d = await request.json()\n"
        "        return web.json_response({'r': d.get('a')})\n"
        "    async def hq(self, request):\n"
        "        d = await request.json()\n"
        "        return web.json_response({'r': d.get('b')})\n"
        "class C:\n"
        "    async def _post_json(self, addr, path, payload):\n"
        "        return {}\n"
        "    async def go(self, addr):\n"
        "        payload = {'a': 1}\n"
        "        await self._post_json(addr, '/p', payload)\n"
        "        payload = {'b': 2}\n"
        "        await self._post_json(addr, '/q', payload)\n"
    )
    assert rules_in(src, ["WIRE"]) == []


def test_wire_weak_verb_with_slash_literal_is_not_transport(tmp_path):
    """get/fetch-named helpers taking slash-shaped strings (name-resolve
    keys, file paths) are NOT wire traffic — only an http URL argument
    corroborates a weak verb (was a false WIRE001)."""
    src = tmp_path / "mod.py"
    src.write_text(
        "from aiohttp import web\n"
        "class S:\n"
        "    def build(self):\n"
        "        app = web.Application()\n"
        "        app.add_routes([web.get('/info', self.h)])\n"
        "        return app\n"
        "    async def h(self, request):\n"
        "        return web.json_response({'v': 1})\n"
        "class C:\n"
        "    def get_subtree(self, root):\n"
        "        return []\n"
        "    def fetch_file(self, p):\n"
        "        return b''\n"
        "    def go(self):\n"
        "        self.get_subtree('/rollout/servers')\n"
        "        self.fetch_file('/data/cache')\n"
    )
    assert rules_in(src, ["WIRE"]) == []


def test_wire_dynamic_status_silences_dead_status_check(tmp_path):
    """A handler whose status= is computed may return any code: a client
    branching on one must not fire WIRE004 (was a false dead-branch)."""
    src = tmp_path / "mod.py"
    src.write_text(
        "from aiohttp import web\n"
        "class S:\n"
        "    def build(self):\n"
        "        app = web.Application()\n"
        "        app.add_routes([web.get('/busy', self.h)])\n"
        "        return app\n"
        "    async def h(self, request):\n"
        "        code = 503 if request.query.get('busy') else 200\n"
        "        return web.json_response({'ok': True}, status=code)\n"
        "class C:\n"
        "    async def _get_json(self, addr, path):\n"
        "        return {}\n"
        "    async def go(self, sess, addr):\n"
        "        d = await self._get_json(addr, '/busy')\n"
        "        r = await sess.get(f'http://{addr}/busy')\n"
        "        if r.status == 503:\n"
        "            return None\n"
        "        return d\n"
    )
    assert rules_in(src, ["WIRE"]) == []


def test_lck001_catches_single_statement_two_lock_with(tmp_path):
    """`with self._a, self._b:` vs nested b->a is the idiomatic shape of
    the two-lock inversion — the order edge must be recorded."""
    src = tmp_path / "mod.py"
    src.write_text(
        "import threading\n"
        "class E:\n"
        "    def __init__(self):\n"
        "        self._a = threading.Lock()\n"
        "        self._b = threading.Lock()\n"
        "    def one(self):\n"
        "        with self._a, self._b:\n"
        "            pass\n"
        "    def two(self):\n"
        "        with self._b:\n"
        "            with self._a:\n"
        "                pass\n"
    )
    assert rules_in(src, ["LCK"]) == ["LCK001"]


def test_wire_doc_reads_scoped_to_binding_window(tmp_path):
    """Reads of a name BEFORE the response binds to it (a local dict
    reusing the name) or AFTER a rebind are not response reads — and a
    var bound from two different paths is dropped entirely (both were
    false WIRE003 classes)."""
    server = (
        "from aiohttp import web\n"
        "class S:\n"
        "    def build(self):\n"
        "        app = web.Application()\n"
        "        app.add_routes([web.post('/a', self.ha),\n"
        "                        web.post('/b', self.hb)])\n"
        "        return app\n"
        "    async def ha(self, request):\n"
        "        return web.json_response({'k1': 1})\n"
        "    async def hb(self, request):\n"
        "        return web.json_response({'k2': 2})\n"
    )
    src = tmp_path / "mod.py"
    src.write_text(
        server
        + "class C:\n"
        "    async def _post_json(self, addr, path, payload):\n"
        "        return {}\n"
        "    async def pre_binding_read(self, addr):\n"
        "        d = {'cfg': 1}\n"
        "        x = d['cfg']\n"
        "        d = await self._post_json(addr, '/a', {})\n"
        "        return x, d.get('k1')\n"
        "    async def rebound_var(self, addr):\n"
        "        d = await self._post_json(addr, '/a', {})\n"
        "        x = d['k1']\n"
        "        d = await self._post_json(addr, '/b', {})\n"
        "        return x, d['k2']\n"
    )
    assert rules_in(src, ["WIRE"]) == []


def test_wire_routeless_client_file_is_silent(tmp_path):
    """Unknown is silent: a file outside the package with client calls
    but NO route table of its own (a standalone script talking to an
    external service) must not fire WIRE001 — there is no contract to
    check against. Only files carrying both sides get route checks."""
    src = tmp_path / "loner.py"
    src.write_text(
        "class C:\n"
        "    async def _post_json(self, addr, path, payload):\n"
        "        return {}\n"
        "    async def go(self, addr):\n"
        "        await self._post_json(addr, '/anything-at-all', {'k': 1})\n"
    )
    assert rules_in(src, ["WIRE"]) == []


def test_new_family_suppression_roundtrip(tmp_path):
    """Inline suppression + baseline matching both work for the dataflow
    families (they key on scope/token exactly like the one-hop rules)."""
    src = tmp_path / "mod.py"
    src.write_text(
        "import jax\n"
        "def _loop(fn, x):\n"
        "    for _ in range(4):\n"
        "        x = fn(x)\n"
        "    # arealint: disable-next=PRF001 boundary pull with written reason\n"
        "    host = jax.device_get(x)\n"
        "    jax.block_until_ready(x)\n"
        "    return host\n"
    )
    res = run_analysis([src], rules=["PRF"], baseline_path=None)
    assert [f.rule for f in res.findings] == ["PRF001"]  # only the unsuppressed one
    assert len(res.suppressed) == 1
    # baseline round-trip: the surviving finding baselines by key
    doc = render_baseline(res.findings)
    bpath = tmp_path / "b.json"
    bpath.write_text(json.dumps(doc))
    res2 = run_analysis([src], rules=["PRF"], baseline_path=bpath)
    assert res2.findings == []
    assert len(res2.baselined) == 1


def test_prf_key_stable_across_line_shifts(tmp_path):
    original = (FIXTURES / "prf_bad.py").read_text()
    moved = tmp_path / "prf_bad.py"
    moved.write_text("\n\n# header edit\n\n" + original)
    keys = lambda p: sorted(
        f.key.split(":", 2)[2]
        for f in run_analysis([p], rules=["PRF"], baseline_path=None).findings
    )
    assert keys(FIXTURES / "prf_bad.py") == keys(moved)


def test_obs_catalog_lint_rules_exist():
    # catalog-side lint (OBS003/OBS004/OBS005) runs on the real catalog and
    # must be clean — it replaced validate_installation's ad-hoc check
    from areal_tpu.analysis import default_package_root

    cat = default_package_root() / "observability" / "catalog.py"
    assert rules_in(cat, ["OBS"]) == []


# ---------------------------------------------------------------------------
# suppressions
# ---------------------------------------------------------------------------


def test_suppressions():
    res = run_analysis([FIXTURES / "suppress.py"], rules=["ASY"], baseline_path=None)
    # only the marker-inside-a-string sleep survives
    assert len(res.findings) == 1
    assert res.findings[0].key.endswith("not_in_string:time.sleep")
    # the four commented sites were recorded as suppressed, not dropped
    assert len(res.suppressed) == 4


def test_file_level_suppression(tmp_path):
    src = tmp_path / "mod.py"
    src.write_text(
        "# arealint: disable-file=ASY001 fixture-wide reason\n"
        "import time\n"
        "async def a():\n"
        "    time.sleep(1)\n"
        "async def b():\n"
        "    time.sleep(2)\n"
    )
    res = run_analysis([src], rules=["ASY"], baseline_path=None)
    assert res.findings == []
    assert len(res.suppressed) == 2


def test_suppression_reason_parsed():
    sf = SourceFile.load(FIXTURES / "suppress.py", FIXTURES)
    reasons = [s.reason for s in sf.suppressions.values()]
    assert any("dedicated smoke-test coroutine" in r for r in reasons)


# ---------------------------------------------------------------------------
# baseline
# ---------------------------------------------------------------------------


def test_baseline_matches_by_key_and_reports_stale(tmp_path):
    res = run_analysis([FIXTURES / "asy_bad.py"], rules=["ASY"], baseline_path=None)
    assert res.findings
    doc = render_baseline(res.findings[:2])
    doc["findings"].append(
        {"rule": "ASY001", "path": "gone.py", "key": "ASY001:gone.py:f:time.sleep", "reason": "x"}
    )
    bpath = tmp_path / "baseline.json"
    bpath.write_text(json.dumps(doc))
    res2 = run_analysis(
        [FIXTURES / "asy_bad.py"], rules=["ASY"], baseline_path=bpath
    )
    assert len(res2.baselined) == 2
    assert len(res2.findings) == len(res.findings) - 2
    assert [e["path"] for e in res2.stale_baseline] == ["gone.py"]


def test_baseline_key_stable_across_line_shifts(tmp_path):
    original = (FIXTURES / "asy_bad.py").read_text()
    moved = tmp_path / "asy_bad.py"
    moved.write_text("\n\n# shifted by a header edit\n\n" + original)
    keys = lambda p: sorted(
        f.key.split(":", 2)[2]  # drop rule+path (paths differ)
        for f in run_analysis([p], rules=["ASY"], baseline_path=None).findings
    )
    assert keys(FIXTURES / "asy_bad.py") == keys(moved)


def test_render_baseline_carries_reasons_forward():
    res = run_analysis([FIXTURES / "asy_bad.py"], rules=["ASY"], baseline_path=None)
    first = render_baseline(res.findings)
    for e in first["findings"]:
        e["reason"] = "justified: " + e["key"]
    second = render_baseline(res.findings, old=first)
    assert all(e["reason"].startswith("justified: ") for e in second["findings"])


def test_load_baseline_rejects_malformed(tmp_path):
    p = tmp_path / "b.json"
    p.write_text('{"not": "a baseline"}')
    with pytest.raises(ValueError):
        load_baseline(p)


# ---------------------------------------------------------------------------
# ordering + output format
# ---------------------------------------------------------------------------


def test_finding_order_is_stable_and_sorted():
    paths = sorted(FIXTURES.glob("*_bad.py"))
    res1 = run_analysis(paths, baseline_path=None)
    res2 = run_analysis(list(reversed(paths)), baseline_path=None)
    assert [f.key for f in res1.findings] == [f.key for f in res2.findings]
    triples = [(f.path, f.line, f.rule) for f in res1.findings]
    assert triples == sorted(triples)


def test_json_output_schema(capsys):
    rc = cli.main([str(FIXTURES / "asy_bad.py"), "--format", "json", "--no-baseline"])
    out = json.loads(capsys.readouterr().out)
    assert rc == cli.EXIT_FINDINGS
    assert out["version"] == 1 and out["ok"] is False
    f = out["findings"][0]
    assert {"rule", "path", "line", "message", "severity", "key"} <= set(f)


def test_cli_exit_codes(capsys, tmp_path):
    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    assert cli.main([str(clean), "--no-baseline"]) == cli.EXIT_CLEAN
    assert (
        cli.main([str(FIXTURES / "asy_bad.py"), "--no-baseline"])
        == cli.EXIT_FINDINGS
    )
    assert cli.main([str(tmp_path / "nope.py")]) == cli.EXIT_ERROR
    capsys.readouterr()


def test_cli_list_rules(capsys):
    assert cli.main(["--list-rules"]) == cli.EXIT_CLEAN
    out = capsys.readouterr().out
    for family_rule in ("ASY001", "JAX005", "THR001", "CFG003", "OBS001"):
        assert family_rule in out


def test_cli_write_baseline_roundtrip(tmp_path, capsys):
    bpath = tmp_path / "baseline.json"
    rc = cli.main(
        [str(FIXTURES / "thr_bad.py"), "--baseline", str(bpath), "--write-baseline"]
    )
    assert rc == cli.EXIT_CLEAN
    doc = load_baseline(bpath)
    assert doc["findings"]
    # now the same run against the written baseline is clean
    rc = cli.main([str(FIXTURES / "thr_bad.py"), "--baseline", str(bpath)])
    assert rc == cli.EXIT_CLEAN
    capsys.readouterr()


def test_parse_error_is_a_finding(tmp_path):
    bad = tmp_path / "broken.py"
    bad.write_text("def broken(:\n")
    res = run_analysis([bad], baseline_path=None)
    assert [f.rule for f in res.findings] == ["PARSE"]


def test_rule_filter_by_id():
    analyzer = Analyzer(rules=["ASY001"])
    res = analyzer.run([FIXTURES / "asy_bad.py"])
    assert {f.rule for f in res.findings} == {"ASY001"}


def test_cfg_nested_shadowing_param_not_confused(tmp_path):
    # an inner function whose parameter shadows an outer config-typed name
    # must not inherit the outer type (was a false CFG001)
    src = tmp_path / "mod.py"
    src.write_text(
        "from areal_tpu.api.config import PPOActorConfig\n"
        "def outer(cfg: PPOActorConfig):\n"
        "    ok = cfg.group_size\n"
        "    def inner(cfg):\n"
        "        return cfg.not_a_field_anywhere\n"
        "    return ok, inner\n"
    )
    assert rules_in(src, ["CFG"]) == []


def test_cfg_nested_closure_still_checked(tmp_path):
    # a nested function that CLOSES OVER the outer config var is checked
    # with the inherited environment
    src = tmp_path / "mod.py"
    src.write_text(
        "from areal_tpu.api.config import PPOActorConfig\n"
        "def outer(cfg: PPOActorConfig):\n"
        "    def inner():\n"
        "        return cfg.group_syze\n"
        "    return inner\n"
    )
    assert rules_in(src, ["CFG"]) == ["CFG001"]


def test_asy004_scoped_to_class(tmp_path):
    # A.flush blocks, B.flush does not: async B code calling self.flush()
    # must not be blamed for A's body (was a false ASY004)
    src = tmp_path / "mod.py"
    src.write_text(
        "import time\n"
        "class A:\n"
        "    def flush(self):\n"
        "        time.sleep(1)\n"
        "class B:\n"
        "    def flush(self):\n"
        "        pass\n"
        "    async def run(self):\n"
        "        self.flush()\n"
        "class C:\n"
        "    async def run(self):\n"
        "        self.flush()  # no local def at all: unknown, no finding\n"
    )
    assert rules_in(src, ["ASY"]) == []
    src.write_text(
        "import time\n"
        "class A:\n"
        "    def flush(self):\n"
        "        time.sleep(1)\n"
        "    async def run(self):\n"
        "        self.flush()\n"
    )
    assert rules_in(src, ["ASY"]) == ["ASY004"]


def test_jax_nested_helper_reported_once(tmp_path):
    src = tmp_path / "mod.py"
    src.write_text(
        "import jax\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    def helper(y):\n"
        "        print(y)\n"
        "        return y\n"
        "    return helper(x)\n"
    )
    res = run_analysis([src], rules=["JAX"], baseline_path=None)
    assert [f.rule for f in res.findings] == ["JAX001"]


def test_suppression_covers_multiline_statement(tmp_path):
    src = tmp_path / "mod.py"
    src.write_text(
        "import time\n"
        "async def f():\n"
        "    time.sleep(\n"
        "        1.0\n"
        "    )  # arealint: disable=ASY001 trailing comment after the paren\n"
    )
    res = run_analysis([src], rules=["ASY"], baseline_path=None)
    assert res.findings == []
    assert len(res.suppressed) == 1


def test_standalone_comment_does_not_blanket_enclosing_block(tmp_path):
    src = tmp_path / "mod.py"
    src.write_text(
        "import time\n"
        "async def f():\n"
        "    # arealint: disable=ASY001\n"
        "    x = 1\n"
        "    time.sleep(1.0)\n"  # two lines below the bare comment
    )
    res = run_analysis([src], rules=["ASY"], baseline_path=None)
    assert [f.rule for f in res.findings] == ["ASY001"]


def test_unknown_rule_selection_is_an_error(capsys):
    with pytest.raises(ValueError):
        Analyzer(rules=["ASY01"])  # typo must not silently check nothing
    rc = cli.main(["--rules", "NOPE123", str(FIXTURES / "asy_bad.py")])
    assert rc == cli.EXIT_ERROR
    assert "unknown rule" in capsys.readouterr().err


def test_write_baseline_refuses_rule_filter(tmp_path, capsys):
    bpath = tmp_path / "b.json"
    rc = cli.main(
        [
            str(FIXTURES / "asy_bad.py"),
            "--rules", "ASY",
            "--baseline", str(bpath),
            "--write-baseline",
        ]
    )
    assert rc == cli.EXIT_ERROR
    assert not bpath.exists()
    capsys.readouterr()


def test_cli_sarif_output(capsys):
    rc = cli.main(
        [str(FIXTURES / "shd_bad.py"), "--format", "sarif", "--no-baseline"]
    )
    assert rc == cli.EXIT_FINDINGS
    doc = json.loads(capsys.readouterr().out)
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "arealint"
    rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert {"SHD001", "SHD002", "SHD003"} <= rule_ids
    res = run["results"][0]
    loc = res["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"].endswith("shd_bad.py")
    assert loc["region"]["startLine"] > 0
    # line-independent identity for CI annotation dedup
    assert res["partialFingerprints"]["arealintKey"].startswith(res["ruleId"])


def test_cli_sarif_clean_is_exit_zero(capsys, tmp_path):
    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    rc = cli.main([str(clean), "--format", "sarif", "--no-baseline"])
    assert rc == cli.EXIT_CLEAN
    doc = json.loads(capsys.readouterr().out)
    assert doc["runs"][0]["results"] == []


def test_cli_changed_only_empty_set_is_clean(tmp_path, capsys, monkeypatch):
    """Exit-code contract: an empty changed set exits 0 with a loud note
    (documented in the CLI help)."""
    repo = tmp_path / "repo"
    (repo / "pkg").mkdir(parents=True)
    (repo / "pkg" / "mod.py").write_text("import time\n")
    monkeypatch.setattr(cli, "changed_python_files", lambda root: [])
    rc = cli.main([str(repo / "pkg"), "--changed-only", "--no-baseline"])
    assert rc == cli.EXIT_CLEAN
    out = capsys.readouterr().out
    assert "no changed .py files" in out


def test_cli_changed_only_scopes_to_diff(tmp_path, capsys, monkeypatch):
    """Only the intersection of (changed files, requested paths) is
    analyzed: the dirty file outside the requested path is ignored and
    the unchanged bad file inside it is not scanned."""
    import subprocess

    from areal_tpu.tools import arealint as cli_mod

    changed = tmp_path / "changed.py"
    changed.write_text("import time\nasync def f():\n    time.sleep(1)\n")
    unchanged = tmp_path / "unchanged.py"
    unchanged.write_text("import time\nasync def g():\n    time.sleep(2)\n")
    outside = tmp_path / "outside.py"
    outside.write_text("import time\nasync def h():\n    time.sleep(3)\n")

    def fake_changed(repo_root):
        return [changed, outside]

    monkeypatch.setattr(cli_mod, "changed_python_files", fake_changed)
    rc = cli_mod.main(
        [str(changed), str(unchanged), "--changed-only", "--no-baseline"]
    )
    out = capsys.readouterr().out
    assert rc == cli_mod.EXIT_FINDINGS
    assert "changed.py" in out
    assert "unchanged.py" not in out
    assert "outside.py" not in out


def test_cli_changed_only_rejects_write_baseline(capsys):
    rc = cli.main(["--changed-only", "--write-baseline"])
    assert rc == cli.EXIT_ERROR
    assert "--changed-only" in capsys.readouterr().err


def test_changed_python_files_in_this_repo(tmp_path):
    """Against a real throwaway git repo: diffed + untracked .py files
    are returned, committed-clean ones are not."""
    import subprocess

    repo = tmp_path / "r"
    repo.mkdir()
    env = {"GIT_AUTHOR_NAME": "t", "GIT_AUTHOR_EMAIL": "t@t",
           "GIT_COMMITTER_NAME": "t", "GIT_COMMITTER_EMAIL": "t@t"}
    import os

    def git(*a):
        subprocess.run(
            ["git", *a], cwd=repo, check=True, capture_output=True,
            env={**os.environ, **env},
        )

    git("init", "-q")
    (repo / "clean.py").write_text("x = 1\n")
    # project root NESTED under the git toplevel (monorepo layout): diff
    # output must stay relative to the project root, not the toplevel
    sub = repo / "proj"
    sub.mkdir()
    (sub / "inner.py").write_text("z = 1\n")
    git("add", "clean.py", "proj/inner.py")
    git("commit", "-qm", "init")
    (repo / "clean.py").write_text("x = 2\n")  # modified
    (repo / "fresh.py").write_text("y = 1\n")  # untracked
    (sub / "inner.py").write_text("z = 2\n")  # modified in the subdir
    got = {p.name for p in cli.changed_python_files(repo)}
    assert got == {"clean.py", "fresh.py", "inner.py"}
    # scanning FROM the nested project root sees only its own subtree
    got_sub = {p.name for p in cli.changed_python_files(sub)}
    assert got_sub == {"inner.py"}


def test_changed_python_files_unborn_head(tmp_path):
    """A worktree before its first commit is still a worktree: staged and
    untracked files are reported (empty-tree diff fallback), not a
    misleading 'needs a git worktree' error."""
    import os
    import subprocess

    repo = tmp_path / "r"
    repo.mkdir()
    env = {"GIT_AUTHOR_NAME": "t", "GIT_AUTHOR_EMAIL": "t@t",
           "GIT_COMMITTER_NAME": "t", "GIT_COMMITTER_EMAIL": "t@t"}

    def git(*a):
        subprocess.run(
            ["git", *a], cwd=repo, check=True, capture_output=True,
            env={**os.environ, **env},
        )

    git("init", "-q")
    (repo / "staged.py").write_text("a = 1\n")
    git("add", "staged.py")
    (repo / "loose.py").write_text("b = 1\n")
    got = {p.name for p in cli.changed_python_files(repo)}
    assert got == {"staged.py", "loose.py"}


def test_cli_changed_only_suppresses_stale_baseline_noise(
    tmp_path, capsys, monkeypatch
):
    """A diff-scoped run cannot prove baseline entries stale — it must
    not print the stale advice for out-of-scope entries."""
    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    bpath = tmp_path / "b.json"
    bpath.write_text(json.dumps({"version": 1, "findings": [
        {"rule": "ASY001", "path": "elsewhere.py",
         "key": "ASY001:elsewhere.py:f:time.sleep", "reason": "r"}
    ]}))
    monkeypatch.setattr(cli, "changed_python_files", lambda root: [clean])
    rc = cli.main(
        [str(clean), "--changed-only", "--baseline", str(bpath)]
    )
    out = capsys.readouterr().out
    assert rc == cli.EXIT_CLEAN
    assert "stale baseline" not in out


def test_write_baseline_preserves_out_of_scope_entries(tmp_path, capsys):
    # seed a baseline from one fixture, then rewrite scoped to ANOTHER:
    # the first fixture's entries (and reasons) must survive the rewrite
    bpath = tmp_path / "b.json"
    assert (
        cli.main(
            [str(FIXTURES / "thr_bad.py"), "--baseline", str(bpath), "--write-baseline"]
        )
        == cli.EXIT_CLEAN
    )
    doc = load_baseline(bpath)
    for e in doc["findings"]:
        e["reason"] = "documented single-writer"
    bpath.write_text(json.dumps(doc))
    assert (
        cli.main(
            [str(FIXTURES / "asy_bad.py"), "--baseline", str(bpath), "--write-baseline"]
        )
        == cli.EXIT_CLEAN
    )
    merged = load_baseline(bpath)
    thr = [e for e in merged["findings"] if e["rule"].startswith("THR")]
    asy = [e for e in merged["findings"] if e["rule"].startswith("ASY")]
    assert thr and asy
    assert all(e["reason"] == "documented single-writer" for e in thr)
    capsys.readouterr()
