"""One general traffic generator, driven by the parameter file of a mix.

Pure numpy and the standard library: the load generator's child process
imports this module and must never import jax. Everything is a function of
the parameters and the seed; the same seed gives the same inputs.

Length distributions (``{"dist": ...}``):
  loguniform  lo, hi             integer, log-uniform on [lo, hi]
  lognormal   median, sigma, lo, hi   integer, clipped to [lo, hi]
  fixed       value

Generators (``{"generator": ...}``):
  grpo_groups   closed-loop GRPO rollout: a stream of groups per client,
                each ``group_size`` requests sharing one prompt
  packed_rows   one training batch: sequences drawn from another mix's
                prompt+output lengths, cut to fill ``rows`` x ``row_len``
"""

from __future__ import annotations

import math

import numpy as np


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """Independent stream per (seed, stream...). ``seed`` may exceed 2**31."""
    return np.random.default_rng([int(seed), *[int(s) for s in stream]])


def draw_lengths(spec: dict, rng: np.random.Generator, n: int) -> np.ndarray:
    dist = spec["dist"]
    if dist == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    lo, hi = int(spec["lo"]), int(spec["hi"])
    if dist == "loguniform":
        x = np.exp(rng.uniform(math.log(lo), math.log(hi), n))
    elif dist == "lognormal":
        x = float(spec["median"]) * np.exp(float(spec["sigma"]) * rng.standard_normal(n))
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def draw_group(mix: dict, rng: np.random.Generator, vocab: int, first_wave: bool) -> dict:
    """One GRPO group: a prompt and ``group_size`` output lengths.

    prompt + output is clipped to ``max_total``. In a client's first group
    the output lengths are scaled by u~U(0,1) so slots start out of phase."""
    g = int(mix["group_size"])
    plen = int(draw_lengths(mix["prompt_len"], rng, 1)[0])
    out = draw_lengths(mix["output_len"], rng, g)
    if first_wave and mix.get("first_wave_scale") == "uniform01":
        lo = int(mix["output_len"]["lo"])
        out = np.maximum(lo, np.rint(out * rng.uniform(0.0, 1.0, g))).astype(np.int64)
    out = np.minimum(out, int(mix["max_total"]) - plen)
    prompt = rng.integers(0, vocab, plen, dtype=np.int64)
    return {"prompt": prompt.tolist(), "new_tokens": [int(x) for x in out]}


class GroupStream:
    """The groups of one closed-loop client, in order.

    The lengths (prompt, outputs, first-wave scaling) are a function of the
    mix alone: client ``k`` carries stream ``k`` of ``shape_seed``. A run's
    seed decides every token id (and, in the cell, the weights and the
    sampling noise), nothing else, so that every seed offers the same work
    in the same order: with 3,000-token outputs a request can outlast the
    whole window, and lengths drawn from the seed made one run's window 20%
    busier than the next's; dealing the streams to other clients by seed
    still moved the rate by 3% (PERF.md, PR 23)."""

    def __init__(self, mix: dict, seed: int, client: int, vocab: int):
        if mix["generator"] != "grpo_groups":
            raise ValueError(f"not a grpo_groups mix: {mix['generator']!r}")
        self.mix, self.vocab = mix, int(vocab)
        self.shape_rng = rng_for(int(mix["shape_seed"]), 1, client)
        self.token_rng = rng_for(seed, 5, client)
        self.n = 0

    def next(self) -> dict:
        grp = draw_group(self.mix, self.shape_rng, 2, first_wave=self.n == 0)
        grp["prompt"] = self.token_rng.integers(0, self.vocab, len(grp["prompt"]), dtype=np.int64).tolist()
        self.n += 1
        return grp


def sequence_lengths(mix: dict, rng: np.random.Generator, n: int) -> np.ndarray:
    """prompt+output lengths of ``n`` single samples of a grpo_groups mix,
    with the prompt lengths beside them."""
    p = draw_lengths(mix["prompt_len"], rng, n)
    o = np.minimum(draw_lengths(mix["output_len"], rng, n), int(mix["max_total"]) - p)
    return p, p + o


def ffd_rows(lens, cap: int) -> list[list[int]]:
    """First-fit-decreasing bins of capacity ``cap``: indices of ``lens`` a
    row. The packing rule of the trainer's grids, restated here so that a
    batch's row count is known before the program sees it."""
    rows: list[list[int]] = []
    free: list[int] = []
    for i in sorted(range(len(lens)), key=lambda i: -lens[i]):
        r = next((r for r, f in enumerate(free) if f >= lens[i]), None)
        if r is None:
            rows.append([])
            free.append(cap)
            r = len(rows) - 1
        rows[r].append(i)
        free[r] -= lens[i]
    return rows


def packed_shapes(mix: dict, lengths_mix: dict) -> list[tuple[int, int]]:
    """The (prompt_len, total_len) multiset of a packed_rows mix, a function
    of the mix alone. Sequences are drawn from ``lengths_mix`` until they
    hold rows*row_len tokens, the last one cut to that total; the shortest
    are dropped until first-fit-decreasing needs no more than ``rows`` rows;
    then each row's free tail is given to its shortest sequence where the
    packing survives it."""
    rows, cap = int(mix["rows"]), int(mix["row_len"])
    min_len = int(mix.get("min_len", 32))
    rng = rng_for(int(mix["shape_seed"]), 2)
    seqs: list[list[int]] = []
    total = 0
    while total < rows * cap:
        p, t = sequence_lengths(lengths_mix, rng, 1)
        p, t = int(p[0]), min(int(t[0]), cap, rows * cap - total)
        if t < min_len:
            break
        seqs.append([min(p, max(1, t // 2)), t])
        total += t
    while len(ffd_rows([t for _, t in seqs], cap)) > rows:
        seqs.remove(min(seqs, key=lambda s: s[1]))
    for _ in range(rows):
        lens = [t for _, t in seqs]
        packing = ffd_rows(lens, cap)
        frees = [cap - sum(lens[i] for i in row) for row in packing]
        r = max(range(len(packing)), key=lambda r: frees[r])
        if frees[r] < 1:
            break
        i = min(packing[r], key=lambda i: lens[i])
        seqs[i][1] += frees[r]
        if len(ffd_rows([t for _, t in seqs], cap)) > rows:
            seqs[i][1] -= frees[r]
            break
    return [(p, t) for p, t in seqs]


def packed_batch(mix: dict, lengths_mix: dict, seed: int, vocab: int) -> list[dict]:
    """One training batch of a packed_rows mix.

    The multiset of sequence lengths is a function of the mix alone
    (``packed_shapes``): every ``--seed`` trains on the same shapes, in
    another order and with other tokens, so the compiled program and the
    token count never change with the seed."""
    if mix["generator"] != "packed_rows":
        raise ValueError(f"not a packed_rows mix: {mix['generator']!r}")
    seqs = packed_shapes(mix, lengths_mix)
    rng = rng_for(seed, 3)
    order = rng.permutation(len(seqs))
    out = []
    for i in order:
        p, t = seqs[i]
        old = rng.normal(float(mix["logprob_mean"]), float(mix["logprob_std"]), t)
        out.append(
            {
                "input_ids": rng.integers(0, vocab, t, dtype=np.int64).astype(np.int32),
                "loss_mask": np.concatenate(
                    [np.zeros(p, np.float32), np.ones(t - p, np.float32)]
                ),
                "old_logprobs": old.astype(np.float32),
                "prox_logprobs": (old + rng.normal(0, float(mix["prox_noise"]), t)).astype(
                    np.float32
                ),
                "advantages": rng.normal(0, 1, t).astype(np.float32),
            }
        )
    return out
