"""Seeded weights, made on the device in one jitted call, in the served type.

The benchmark makes the weights, not the program: the plain reference takes
its inputs from the seed and nothing the program has made. The tree has the
layout the program's Qwen2 forward reads (stacked layers), which is the
system's interface; the values come from here. Norm weights and the q/k/v
biases are random too, so a forward that dropped them would not agree with
the reference.
"""

from __future__ import annotations

from benchlib import peaks


def shapes(cfg: dict) -> dict:
    """{"embed": (V, D), "layers": {name: (n_layers, ...)}, ...} from the
    configuration file's published fields."""
    D = int(cfg["hidden_size"])
    F = int(cfg["intermediate_size"])
    n = int(cfg["num_hidden_layers"])
    hd = peaks.dims(cfg)["hd"]
    Q = int(cfg["num_attention_heads"]) * hd
    KV = int(cfg["num_key_value_heads"]) * hd
    layers = {
        "wq": (n, D, Q),
        "wk": (n, D, KV),
        "wv": (n, D, KV),
        "wo": (n, Q, D),
        "w_gate": (n, D, F),
        "w_up": (n, D, F),
        "w_down": (n, F, D),
        "input_norm": (n, D),
        "post_attn_norm": (n, D),
        "bq": (n, Q),
        "bk": (n, KV),
        "bv": (n, KV),
    }
    out = {"embed": (int(cfg["vocab_size"]), D), "layers": layers, "final_norm": (D,)}
    if not cfg["tie_word_embeddings"]:
        out["lm_head"] = (int(cfg["vocab_size"]), D)
    return out


def seed_key(seed: int):
    """A PRNG key from any whole-number seed, also above 2**31."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def make_params(cfg: dict, seed: int, dtype, shardings=None) -> dict:
    """Every leaf drawn on the device, directly in ``dtype``, in one program."""
    import jax

    shp = shapes(cfg)
    std = float(cfg.get("initializer_range", 0.02))

    def build(key):
        flat, treedef = jax.tree.flatten_with_path(shp, is_leaf=lambda x: isinstance(x, tuple))
        keys = jax.random.split(key, len(flat))
        leaves = []
        for k, (path, shape) in zip(keys, flat):
            name = path[-1].key
            x = jax.random.normal(k, shape, dtype)
            if name.endswith("norm"):
                x = 1.0 + 0.1 * x
            else:
                x = std * x
            leaves.append(x.astype(dtype))
        return jax.tree.unflatten(treedef, leaves)

    return jax.jit(build, out_shardings=shardings)(seed_key(seed))


def kept_leaves(tree: dict, keep: dict) -> dict:
    """The part of a weight-shaped tree (the parameters, an optimizer moment)
    that the train cell's output check compares, under the names
    ``reference.grpo_grads`` gives its gradients: the final norm, the first
    ``rows`` rows of the embedding (and of an untied head), the per-layer
    ``vectors`` of every layer, and the ``matrices`` of the listed layers."""
    rows = int(keep["rows"])
    out = {"final_norm": tree["final_norm"], "embed": tree["embed"][:rows]}
    if "lm_head" in tree:
        out["lm_head"] = tree["lm_head"][:rows]
    for k in keep["vectors"]:
        out[k] = tree["layers"][k]
    for i in keep["layers"]:
        for k in keep["matrices"]:
            out[f"{k}.{i}"] = tree["layers"][k][i]
    return out
