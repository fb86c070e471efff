"""``sdar_moe`` (Qwen3-MoE's layer, generation by diffusion over blocks of 4
positions) through the engine's own programs, a pass at a time, against the
benchmark's plain reference: the block pass and the commit pass over a paged
cache for all three remasking rules, the block-causal prompt pass and the
suffix prompt pass behind a cached prefix, the rule's choice among candidates
the test can see, and the reference's two forms against each other.

Tiny size (3 layers, hidden 32, 8 experts top-2, vocabulary 300 or 16),
float32, seeded weights; logits and log-probabilities, never sampled tokens
alone.

Tolerances: float32 on both sides, logits of order 1: 2e-5 on log-softmax rows
and log-probabilities (measured 3e-6). A block attended causally, a key
written from a pass that still held the mask, a position re-masked because its
id is the mask token's, or a candidate read from the row before its own moves
a log-probability by 1e-2 and more."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmark_harness"))
import chipbench_sdar_util as su  # noqa: E402
import family_harness as fh  # noqa: E402
from chipbench_util import load_run  # noqa: E402

load_run()

from areal_tpu.api.config import MeshConfig, ServerConfig  # noqa: E402
from areal_tpu.inference.decode_programs import DecodePrograms, pack_row, select_positions, slot_state  # noqa: E402
from areal_tpu.models import qwen  # noqa: E402

TOL = 2e-5
PSZ, T, SLOTS, SLOT = 16, 64, 3, 1
RULES = qwen.REMASKING_RULES


@functools.lru_cache(maxsize=None)
def _programs(mcfg, kernel: bool = False) -> DecodePrograms:
    """The engine's programs of a configuration, kept for the worker's life: a second request at it traces nothing.
    ``kernel``: the chip's path (the in-block Pallas launch and the row writer's), interpreted here."""
    from areal_tpu.parallel import mesh as mesh_lib

    scfg = ServerConfig(
        dtype="float32", max_batch_size=SLOTS, max_seq_len=T, page_size=PSZ, decode_steps_per_call=1, attn_window_step=T, seed=0,
        mesh=MeshConfig(data=1, fsdp=1, seq=1, model=1),
    )
    progs = DecodePrograms(qwen, mcfg, scfg, mesh_lib.make_mesh(scfg.mesh, devices=jax.devices()[:1]))
    if kernel:
        progs.set_suffix_kernel(True)
    return progs


@functools.lru_cache(maxsize=None)
def _block_logp(mcfg, use_kernel: bool = False):
    def logp(params, blk, start, live, cache, table):
        hidden, *_ = qwen.forward_block_paged(params, mcfg, blk, start, live, cache, table, use_kernel=use_kernel)
        return jax.nn.log_softmax(qwen.compute_logits(params, mcfg, hidden), axis=-1)

    return jax.jit(logp)


class Served:
    """One request in slot ``SLOT`` of the engine's programs, stepped a pass at
    a time: prefill, the admission scatter, then one-pass chunks."""

    def __init__(self, cfg: dict, params, prompt, budget: int, rule: str, steps: int = 2, thresh: float = 0.9, temp: float = 1.0, seed: int = 0, kernel: bool = False):
        self.cfg, self.params, self.prompt = cfg, params, list(prompt)
        self.mcfg = su.model_config(cfg)
        self.B = B = self.mcfg.block_length
        self.progs = _programs(self.mcfg, kernel)
        self.cache, table = fh.fresh_cache(self.mcfg, SLOTS, T // PSZ, PSZ)
        table[[s for s in range(SLOTS) if s != SLOT]] = 0  # a slot without a request: the trash page, as the engine leaves it
        self.table = jnp.asarray(table)
        P = len(prompt)
        ids = np.full((1, 32), fh.PAD, np.int32)
        ids[0, :P] = prompt
        prefill, args = self.progs.prefill_call(ids, np.asarray([P], np.int32), table[SLOT : SLOT + 1, : 32 // PSZ], np.asarray([SLOT], np.int32))
        self.cache = prefill(params, self.cache, *args)
        host = slot_state(SLOTS, B)
        self.start = P // B * B
        row = pack_row(
            host, SLOT, prompt[-1], self.start, True, budget, temp=temp,
            block=(B // steps, RULES.index(rule), thresh, list(prompt[self.start :])),
        )
        apply, args = self.progs.update_call([row])
        self.state = apply(jax.tree.map(jnp.asarray, host), *args)
        self.rng = jax.random.PRNGKey(seed)
        self.tokens, self.logps, self.passes, self.versions = [], [], [], []

    def block_inputs(self):
        """(the ids the next pass feeds the slot's block, its first position, its masked flags)."""
        st = jax.tree.map(np.asarray, self.state)
        masked = st["blk_masked"][SLOT]
        return np.where(masked, self.mcfg.mask_token_id, st["blk_ids"][SLOT]), int(st["pos"][SLOT]), masked

    def block_logp(self):
        """The program's log-softmax rows [B, V] of the pass about to run."""
        ids, start, _ = self.block_inputs()
        blk = jnp.zeros((SLOTS, self.B), jnp.int32).at[SLOT].set(jnp.asarray(ids))
        start = jnp.zeros(SLOTS, jnp.int32).at[SLOT].set(start)
        return np.asarray(_block_logp(self.mcfg)(self.params, blk, start, jnp.arange(SLOTS) == SLOT, self.cache, self.table))[SLOT]

    def one_pass(self, version: int = 0) -> int:
        """Run one pass; returns how many tokens it emitted."""
        chunk = self.progs.chunk_fn(1, T // PSZ, False, False)
        self.cache, self.state, self.rng, packed = chunk(self.params, self.cache, self.table, self.state, self.rng, jnp.int32(version))
        packed, B = np.asarray(packed), self.B
        n = int(packed[4 * B, SLOT])
        self.tokens += packed[:n, SLOT].tolist()
        self.logps += packed[B : B + n, SLOT].view(np.float32).tolist()
        self.passes += packed[2 * B : 2 * B + n, SLOT].tolist()
        self.versions += packed[3 * B : 3 * B + n, SLOT].tolist()
        self.counts = packed[4 * B + 3 :].reshape(-1)
        return n

    @property
    def active(self) -> bool:
        return bool(np.asarray(self.state["active"])[SLOT])

    def slot_pages(self, name: str = "k"):
        """Layer 0's K rows of the slot's pages, as [positions, KH, hd]."""
        pages = np.asarray(self.cache[name])[0][:, np.asarray(self.table)[SLOT]]  # [KH, wp, psz, hd]
        return np.transpose(pages, (1, 2, 0, 3)).reshape(T, pages.shape[0], -1)


@pytest.fixture(scope="module")
def model():
    cfg = su.tiny_model()
    return cfg, su.make_params(cfg, 23)


def _prompt(n: int, seed: int = 0, vocab: int = 300):
    return np.random.default_rng(seed).integers(0, vocab, n).tolist()


@pytest.mark.parametrize("rule", RULES)
def test_every_pass_of_a_request_reads_the_references_logits_and_only_a_clean_block_reaches_its_pages(model, rule):
    """A prompt of 9 tokens (one past a block boundary), 10 new tokens (the
    last block cut to 2 of 4): before every pass the program's log-softmax
    rows of the block equal the reference's pass over the SAME block state on
    top of the reference's own clean blocks; a denoise pass leaves the slot's
    pages as they were; the commit pass writes the reference's clean keys; the
    emitted log-probabilities equal ``trace_logprobs`` at the reported passes."""
    cfg, params = model
    ref = su.reference()
    d = ref.dims(cfg)
    prompt = _prompt(9)
    run = Served(cfg, params, prompt, budget=10, rule=rule, thresh=0.02 if rule == RULES[2] else 0.9)
    B, P = run.B, len(prompt)
    past = [None] * d["L"]
    with jax.default_matmul_precision("highest"):
        for start in range(0, P // B * B, B):  # the prompt's whole blocks, clean
            _, kv = ref._block_pass(params, d, np.asarray(prompt[start : start + B]), start, past)
            past = [kv[i] if past[i] is None else tuple(jnp.concatenate([a, b]) for a, b in zip(past[i], kv[i])) for i in range(d["L"])]
    n_pass = 0
    while run.active:
        ids_in, start, masked = run.block_inputs()
        with jax.default_matmul_precision("highest"):
            want, kv = ref._block_pass(params, d, ids_in, start, past)
        np.testing.assert_allclose(run.block_logp(), np.asarray(want), atol=TOL, rtol=0)
        before = run.slot_pages()
        emitted = run.one_pass()
        after = run.slot_pages()
        open_now = masked[: min(B, P + 10 - start)].any()  # masked positions the budget still reaches
        if open_now:
            assert emitted == 0
            np.testing.assert_array_equal(after, before)  # a denoise pass writes nothing
        else:
            assert emitted > 0
            np.testing.assert_allclose(after[start : start + B], np.asarray(kv[0][0]), atol=TOL, rtol=0)  # the clean block's keys
            np.testing.assert_array_equal(after[:start], before[:start])
            past = [tuple(jnp.concatenate([a, b]) for a, b in zip(past[i], kv[i])) for i in range(d["L"])]
        n_pass += 1
        assert n_pass < 40
    assert len(run.tokens) == 10 and len(run.passes) == 10
    want = ref.trace_logprobs(params, cfg, prompt + run.tokens, P, run.passes, pad_to=32)
    np.testing.assert_allclose(run.logps, want, atol=TOL, rtol=0)
    # the counts the chunk hands back: the last pass was a commit pass of one live slot
    counts = dict(zip(run.mcfg.count_shapes, run.counts[:4]))
    assert counts["blk_commit_passes"] == 1 and counts["blk_denoise_passes"] == 0 and counts["blk_blocks"] == 1
    assert counts["blk_attn_tokens_read"] == SLOTS * T  # the gather path reads every slot's whole window


def test_sequential_commits_the_numbers_the_rule_implies_from_the_prompts_length(model):
    """k = 2 of 4: a prompt of 4m + r tokens leaves 4 - r open positions in its
    first block; the last block is cut to the budget."""
    cfg, params = model
    for P, want in ((8, [0, 0, 1, 1, 0, 0, 1]), (9, [0, 0, 1, 0, 0, 1, 1]), (10, [0, 0, 0, 0, 1, 1, 0]), (11, [0, 0, 0, 1, 1, 0, 0])):
        run = Served(cfg, params, _prompt(P, seed=P), budget=7, rule="sequential")
        while run.active:
            run.one_pass()
        assert run.passes == want, (P, run.passes)


def test_masked_is_a_flag_a_prompt_token_and_a_candidate_that_are_the_mask_id_stay_clean():
    """Vocabulary 16 with the mask token's id 5, sampled at temperature 4 so
    that every id comes up: the prompt's tail holds id 5 as a CLEAN token and
    sampling commits id 5 as a candidate. The embedding is the same either
    way; a program that read masked-ness from ``id == mask_token_id`` would
    take the position for masked AGAIN: sample it again, report a later pass
    and take more passes over the block. Every block must take exactly its
    rule's passes and report its rule's numbers."""
    cfg = su.tiny_model(vocab=16, mask_id=5)
    params = su.make_params(cfg, 29)
    prompt = _prompt(9, seed=3, vocab=16) + [5, 5]  # 11 tokens: the first block starts [x, 5, 5 | open]
    run = Served(cfg, params, prompt, budget=21, rule="sequential", temp=4.0, seed=4)
    _, start, masked = run.block_inputs()
    assert start == 8 and masked.tolist() == [False, False, False, True]  # the two 5s of the prompt are clean
    n_pass = 0
    while run.active:
        run.one_pass()
        n_pass += 1
    assert 5 in run.tokens[:-1], run.tokens  # a committed candidate IS the mask id
    # 1 open position, then five whole blocks: (1 + 1) + 5 x (2 + 1) passes
    assert n_pass == 2 + 5 * 3 and run.passes == [0] + [0, 0, 1, 1] * 5


def test_the_confidence_rules_pick_among_candidates_the_test_can_see():
    """``select_positions`` on hand-made candidates: what a reply cannot show."""
    masked = jnp.asarray([[True, True, True, True], [False, True, True, True], [True, False, True, False], [False, False, False, False]])
    logp = jnp.log(jnp.asarray([[0.1, 0.7, 0.2, 0.95], [0.99, 0.3, 0.3, 0.1], [0.5, 0.99, 0.6, 0.99], [0.9, 0.9, 0.9, 0.9]]))
    k, thresh = jnp.asarray([2, 2, 2, 2]), jnp.asarray([0.9, 0.9, 0.55, 0.9])

    def take(rule):
        return np.asarray(select_positions(masked, logp, k, jnp.full(4, RULES.index(rule)), thresh)).astype(int).tolist()

    assert take("sequential") == [[1, 1, 0, 0], [0, 1, 1, 0], [1, 0, 1, 0], [0, 0, 0, 0]]
    # the two most probable MASKED candidates; a tie goes to the earlier position; a clean position's 0.99 counts for nothing
    assert take("low_confidence_static") == [[0, 1, 0, 1], [0, 1, 1, 0], [1, 0, 1, 0], [0, 0, 0, 0]]
    # every masked candidate over the threshold, and the best one whatever it is
    assert take("low_confidence_dynamic") == [[0, 0, 0, 1], [0, 1, 0, 0], [0, 0, 1, 0], [0, 0, 0, 0]]
    mixed = np.asarray(select_positions(masked, logp, k, jnp.asarray([0, 1, 2, 0]), thresh)).astype(int).tolist()
    assert mixed == [[1, 1, 0, 0], [0, 1, 1, 0], [0, 0, 1, 0], [0, 0, 0, 0]]  # a rule a slot, in one pass


def test_a_stop_token_ends_the_request_in_its_block_and_drops_what_follows(model):
    cfg, params = model
    prompt = _prompt(8, seed=5)
    free = Served(cfg, params, prompt, budget=12, rule="sequential", seed=9)
    while free.active:
        free.one_pass()
    stop = free.tokens[5]  # the second token of the second block
    run = Served(cfg, params, prompt, budget=12, rule="sequential", seed=9)
    host = slot_state(SLOTS, run.B)
    row = pack_row(host, SLOT, prompt[-1], 8, True, 12, stops=[stop], block=(2, 0, 0.9, []))
    apply, args = run.progs.update_call([row])
    run.state = apply(run.state, *args)
    while run.active:
        run.one_pass()
    first = free.tokens.index(stop)
    assert run.tokens == free.tokens[: first + 1] and run.tokens[-1] == stop


def test_block_length_one_is_qwen3_moes_own_decode(model):
    """The same weights as ``qwen3_moe``: the block pass over a block of one
    clean position reads the decode step's logits, and the prompt pass's mask
    is the causal one."""
    cfg, params = model
    plain = qwen.ModelConfig.from_hf_dict({**{k: v for k, v in cfg.items() if k != "assumed"}, "model_type": "qwen3_moe"})
    plain = qwen.serving_config(plain, "float32")
    assert plain.block_length == 1 and plain.count_shapes == {"attn_blocks_listed": (1,), "attn_blocks_fetched": (1,)} and plain.moe_count_shapes == {}
    ids = _prompt(13, seed=7)
    cache, table = fh.fresh_cache(plain, SLOTS, T // PSZ, PSZ)
    x = np.full((1, 16), fh.PAD, np.int32)
    x[0, :12] = ids[:12]
    cache = qwen.prefill_into_cache(params, plain, cache, jnp.asarray(x), jnp.asarray([12]), jnp.asarray(table[SLOT, :1]), jnp.asarray([SLOT]), page_size=PSZ)
    tok = jnp.zeros(SLOTS, jnp.int32).at[SLOT].set(ids[12])
    pos = jnp.zeros(SLOTS, jnp.int32).at[SLOT].set(12)
    hidden, _ = qwen.forward_decode_paged(params, plain, tok, pos, dict(cache), jnp.asarray(table), page_size=PSZ, use_kernel=False)
    step = np.asarray(qwen.compute_logits(params, plain, hidden))[SLOT]
    blk, *_ = qwen.forward_block_paged(params, plain, tok[:, None], pos, jnp.arange(SLOTS) == SLOT, cache, jnp.asarray(table))
    np.testing.assert_allclose(np.asarray(qwen.compute_logits(params, plain, blk))[SLOT, 0], step, atol=TOL, rtol=0)
    np.testing.assert_array_equal(np.asarray(qwen._attention_mask(jnp.ones((1, 8), jnp.int32), 1)), np.asarray(qwen._attention_mask(jnp.ones((1, 8), jnp.int32))))


def test_the_references_two_stream_form_is_its_block_by_block_form(model):
    cfg, params = model
    ref = su.reference()
    rng = np.random.default_rng(11)
    for P, n in ((9, 10), (6, 3)):
        ids = rng.integers(0, 300, P + n).tolist()
        # passes as the dynamic rule could report them: any order inside a block, none skipped
        passes = []
        for blk in __import__("benchlib.cells.rollout_family_trace", fromlist=["x"]).blocks_of(P, [0] * n, 4):
            order = rng.permutation(len(blk))
            passes += np.minimum(order, rng.integers(0, len(blk))).tolist() if len(blk) > 1 else [0]
        a = ref.trace_logprobs(params, cfg, ids, P, passes, pad_to=32)
        b = ref.trace_logprobs_by_block(params, cfg, ids, P, passes)
        np.testing.assert_allclose(a, b, atol=TOL, rtol=0)
    # the ids-only form: every block generated whole under sequential at two passes
    ids = rng.integers(0, 300, 18).tolist()
    np.testing.assert_allclose(
        ref.token_logprobs(params, cfg, ids, pad_to=32), ref.trace_logprobs_by_block(params, cfg, ids, 0, ref.sequential_passes(18, 4, 2))[1:], atol=TOL, rtol=0
    )


def test_the_prompt_passes_attend_block_causally_cold_and_behind_a_cached_prefix(model):
    """The keys the prompt pass leaves in the pages are the reference's clean
    blocks' (so a later block reads what generation would have written), cold
    and as a suffix behind one cached page; a causal prompt pass reads
    otherwise."""
    cfg, params = model
    mcfg = su.model_config(cfg)
    ref = su.reference()
    d = ref.dims(cfg)
    ids = _prompt(28, seed=13)  # 7 whole blocks
    past = [None] * d["L"]
    with jax.default_matmul_precision("highest"):
        for start in range(0, 28, 4):
            _, kv = ref._block_pass(params, d, np.asarray(ids[start : start + 4]), start, past)
            past = [kv[i] if past[i] is None else tuple(jnp.concatenate([a, b]) for a, b in zip(past[i], kv[i])) for i in range(d["L"])]
    want = np.asarray(past[d["L"] - 1][0])  # the LAST layer's keys: every layer's attention before them is in them

    def last_layer_keys(cache, table):
        pages = np.asarray(cache["k"])[d["L"] - 1][:, table[SLOT]]
        return np.transpose(pages, (1, 2, 0, 3)).reshape(T, pages.shape[0], -1)[:28]

    cache, table = fh.fresh_cache(mcfg, SLOTS, T // PSZ, PSZ)
    x = np.full((1, 32), fh.PAD, np.int32)
    x[0, :28] = ids
    cold = qwen.prefill_into_cache(params, mcfg, dict(cache), jnp.asarray(x), jnp.asarray([28]), jnp.asarray(table[SLOT, :2]), jnp.asarray([SLOT]), page_size=PSZ)
    np.testing.assert_allclose(last_layer_keys(cold, table), want, atol=TOL, rtol=0)
    causal = qwen.prefill_into_cache(
        params, qwen.dataclasses.replace(mcfg, block_length=1), dict(cache), jnp.asarray(x), jnp.asarray([28]), jnp.asarray(table[SLOT, :2]), jnp.asarray([SLOT]), page_size=PSZ
    )
    assert np.abs(last_layer_keys(causal, table) - want).max() > 1e-2
    # the first page cached (16 tokens, four blocks), the other 12 tokens a suffix over it: both paths of the suffix attention
    for use_kernel in (False, True):
        sfx = np.full((1, 16), fh.PAD, np.int32)
        sfx[0, :12] = ids[16:]
        positions = jnp.asarray(16 + np.arange(16)[None])
        seg = jnp.asarray((np.arange(16) < 12)[None].astype(np.int32))
        _, ks, vs = qwen.forward_prefill_paged(
            params, mcfg, jnp.asarray(sfx), positions, seg, cold, jnp.asarray(table[SLOT : SLOT + 1, :1]), jnp.asarray([16]), use_kernel=use_kernel
        )
        np.testing.assert_allclose(np.asarray(ks)[d["L"] - 1, 0, :12], want[16:], atol=TOL, rtol=0)


def test_the_kernel_path_of_the_block_pass_reads_the_gather_paths_rows(model, monkeypatch):
    """The Pallas suffix launch at 4 rows x 2 group heads a KV head under the
    all-ones mask (interpreted here) against the gather path; then a whole
    request on the chip's path, the row writer interpreted too."""
    from areal_tpu.ops import paged_kv_write as pkw

    monkeypatch.setattr(pkw, "paged_kv_write", functools.partial(pkw.paged_kv_write, interpret=True))
    cfg, params = model
    run = Served(cfg, params, _prompt(22, seed=17), budget=8, rule="sequential")
    ids, start, _ = run.block_inputs()
    blk = jnp.zeros((SLOTS, run.B), jnp.int32).at[SLOT].set(jnp.asarray(ids))
    args = (params, blk, jnp.zeros(SLOTS, jnp.int32).at[SLOT].set(start), jnp.arange(SLOTS) == SLOT, run.cache, run.table)
    a, b = _block_logp(run.mcfg)(*args), _block_logp(run.mcfg, True)(*args)
    np.testing.assert_allclose(np.asarray(b)[SLOT], np.asarray(a)[SLOT], atol=TOL, rtol=0)
    *_, loads = qwen.forward_block_paged(params, run.mcfg, *args[1:])
    assert np.asarray(loads).sum() == run.mcfg.num_layers * run.B * 2  # the live slot's rows x top-2, a layer; no other slot's
    # the whole request on the chip's path: the launch a pass, the committing slot's rows through the row writer a position at a time
    served = Served(cfg, params, run.prompt, budget=9, rule="low_confidence_static", kernel=True)
    while served.active:
        served.one_pass()
    want = su.reference().trace_logprobs(params, cfg, run.prompt + served.tokens, 22, served.passes, pad_to=32)
    np.testing.assert_allclose(served.logps, want, atol=TOL, rtol=0)
    assert dict(zip(served.mcfg.count_shapes, served.counts[:4]))["blk_attn_tokens_read"] == 64  # the live slot's one block of 4 pages
    assert int(qwen.block_attn_tokens_fetched(jnp.asarray([20, 0, 33]), 4, PSZ, True)) == 64 + 0 + 64  # whole blocks of 4 pages
    assert int(qwen.block_attn_tokens_fetched(jnp.asarray([20, 0, 33]), 4, PSZ, False)) == 3 * 64
