"""What the families that serve an explicit LIST of layers share
(``models/mimo_v2.py``, ``models/nemotron_h.py``, ``models/phi4_flash.py``,
``models/jamba.py``):
seeded leaves drawn a layer at a time in the serving dtype, RMSNorm, the
write of a step's or a chunk's keys and values into a layer's slab or ring
(a decode step's through the kernel of ``ops/cache_write.py``), the head's
product, and the plumbing of the expert layers' device counters. What a
layer computes, and what its cache holds, is each family's own.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


# ---- parameters: made in the serving dtype, a layer at a time ----


def draw(key, shapes: dict) -> dict:
    """``path -> (shape, fan_in, dtype)`` as a nested dict of seeded leaves.
    ``fan_in`` None = ones (a norm scale); 0 = zeros (what a freshly made
    model has of a sink logit or a selection bias); else normal with
    variance 1 / fan_in."""
    tree: dict = {}
    for i, (path, (shape, fan_in, dtype)) in enumerate(sorted(shapes.items())):
        if fan_in is None:
            leaf = jnp.ones(shape, dtype)
        elif fan_in == 0:
            leaf = jnp.zeros(shape, dtype)
        else:
            leaf = (
                jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
                * fan_in ** -0.5
            ).astype(dtype)
        node = tree
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[path[-1]] = leaf
    return tree


def outer_shapes(cfg) -> dict:
    """Embedding, final norm and untied head of ``cfg`` (``vocab_size``,
    ``d_model``, ``param_dtype``)."""
    D, V = cfg.d_model, cfg.vocab_size
    return {
        ("embed", "embedding"): ((V, D), 1, cfg.param_dtype),
        ("final_norm", "scale"): ((D,), None, jnp.float32),
        ("lm_head", "kernel"): ((D, V), D, cfg.param_dtype),
    }


def init_params(cfg, key, kinds, init_outer, init_layer) -> dict:
    """The serving tree ``{embed, layers: [per-layer dict], final_norm,
    lm_head}``, each layer made by a program of its own in the serving
    dtype: no float32 copy of the whole tree ever sits on the device (the
    largest transient is one leaf's float32 draw). ``kinds`` is the layers'
    kinds in order (hashable: layers of one kind share a compiled program,
    the layer's index traced); ``init_outer(cfg, key)`` and
    ``init_layer(cfg, kind, key, layer)`` make the leaves."""
    outer = jax.jit(lambda k: init_outer(cfg, k))(key)
    make = jax.jit(
        lambda kind, k, l: init_layer(cfg, kind, k, l), static_argnums=(0,)
    )
    layers = [make(kind, key, jnp.int32(l)) for l, kind in enumerate(kinds)]
    return {**outer, "layers": layers}


# ---- the forward's shared pieces ----


def rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


def write_positions(cache: dict, k, v, positions) -> dict:
    """A layer's cache ``{k, v[, pos]}`` with the incoming keys and values
    ``[B, Hk, S, d]`` written at ``positions [B, S] % length`` of the ``k``
    and ``v`` leaves' position axis; a ring (a cache with a ``pos`` leaf
    ``[B, length]``) also records the positions there.

    A decode step (every row at a place of its own, a step's few positions a
    row: one, or a verifying step's two, at most
    ``ops.cache_attention.STEP_QUERIES``) is ONE pass over the rows a
    position, in the positions' order, with no serial trip a row: both
    leaves through the kernel of ops/cache_write.py, which moves each row's
    one tile and nothing else of the donated leaves, and ``pos`` by a select
    over the leaf, all under the scope ``cache_write`` inside the layer's
    own. A chunk is a scatter, since a ring may wrap inside it.

    **A position written and then given up** (a verifying step's second,
    whose draft the main stack did not choose) needs no undoing: the row's
    next step starts AT that position and writes it again before it attends
    anything. In a slab that is all. In a ring the write at position ``p +
    1`` also REPLACES the entry of ``p + 1 - length``, which the queries at
    ``p`` and later cannot see as long as ``length > window`` (every ring
    here keeps ``window + chunk`` positions), and the entry's recorded
    position is rewritten with it, so no query ever meets keys under
    another position's name."""
    from ..ops.cache_attention import STEP_QUERIES

    idx = positions % cache["k"].shape[2]
    if idx.shape[1] <= STEP_QUERIES:
        from ..ops.cache_write import write_rows

        with jax.named_scope("cache_write"):
            new = dict(cache)
            col = jax.lax.broadcasted_iota(jnp.int32, cache["pos"].shape, 1) if "pos" in cache else None
            for s in range(idx.shape[1]):
                at = idx[:, s]
                new["k"], new["v"] = write_rows((new["k"], new["v"]), (k[:, :, s : s + 1], v[:, :, s : s + 1]), at)
                if col is not None:
                    new["pos"] = jnp.where(col == at[:, None], positions[:, s : s + 1], new["pos"])
        return new
    scatter = lambda slab, vals: jax.vmap(lambda c, u, i: c.at[:, i].set(u))(slab, vals, idx)  # noqa: E731
    new = {"k": scatter(cache["k"], k), "v": scatter(cache["v"], v)}
    if "pos" in cache:
        new["pos"] = jax.vmap(lambda c, u, i: c.at[i].set(u))(cache["pos"], positions, idx)
    return new


def slab_attention(w: dict, cache: dict, x, positions, *, dtype, slot=None, hold=None):
    """Grouped-query attention without a position embedding or bias for ``x
    [B, S, D]`` at ``positions [B, S]`` (contiguous in a row) against a layer's
    full-length slabs ``{k, v} [slots, Hk, L, d]``; the heads' counts and size
    are the leaves' (``q_proj [D, H, d]``, ``k_proj`` / ``v_proj [D, Hk, d]``,
    ``o_proj [H d, D]``). The incoming keys and values are written into the
    slabs first (a chunk at row ``slot``, in place; a decode step a position
    a row), then the queries attend the filled prefix
    (ops/cache_attention.py). A decode step's rows that ``hold [B]`` (None =
    none does) write at the parking position ``L - 1`` that no live stream
    attends, and attend one block, whose result nobody reads
    (``ServingModel.holds``). Returns (out [B, S, D], new cache)."""
    from ..ops.cache_attention import cache_attention

    B, S, _ = x.shape
    (_, H, d), Hk = w["q_proj"].shape, w["k_proj"].shape[1]
    q = jnp.einsum("bsd,dhe->bshe", x, w["q_proj"]).reshape(B, S, Hk, H // Hk, d)
    k = jnp.einsum("bsd,dke->bkse", x, w["k_proj"]).astype(dtype)
    v = jnp.einsum("bsd,dke->bkse", x, w["v_proj"]).astype(dtype)
    if slot is not None:
        at = (slot, 0, positions[0, 0], 0)
        new = {
            "k": jax.lax.dynamic_update_slice(cache["k"], k, at),
            "v": jax.lax.dynamic_update_slice(cache["v"], v, at),
        }
    elif hold is None:
        new = write_positions(cache, k, v, positions)
    else:
        new = write_positions(cache, k, v, jnp.where(hold[:, None], cache["k"].shape[2] - 1, positions))
        positions = jnp.where(hold[:, None], 0, positions)
    out = cache_attention(q, positions, new["k"], new["v"], slot=slot)
    return out.reshape(B, S, H * d) @ w["o_proj"], new


def logits(params: dict, hidden):
    """Float32 logits of ``hidden [..., D]``: the head's product accumulates
    in float32 from the operands as they are held."""
    return jnp.dot(hidden, params["lm_head"]["kernel"], preferred_element_type=jnp.float32)


def tied_logits(params: dict, hidden):
    """Float32 logits of ``hidden [..., D]`` against the embedding (a tied
    head; no head bias), accumulated in float32 from the operands as they are
    held."""
    return jax.lax.dot_general(
        hidden, params["embed"]["embedding"], (((hidden.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def gated_mlp(w: dict, x):
    """SwiGLU without bias, the gate and up projections fused: ``(u *
    silu(g)) W_down`` with ``[g | u] = x W_gate_up``."""
    F = w["down"].shape[0]
    gu = x @ w["gate_up"]
    return (gu[..., F:] * jax.nn.silu(gu[..., :F])) @ w["down"]


def state_cache_bytes(cache: dict) -> dict:
    """The two gauges of a hybrid's cache: bytes held by the attention
    layers' full-length slabs, and by the state-space layers' constant state."""
    size = lambda state: sum(a.size * a.dtype.itemsize for a in state.values())
    return {
        "cache_full_bytes": sum(size(s) for s in cache.values() if "k" in s and "pos" not in s),
        "cache_state_bytes": sum(size(s) for s in cache.values() if "state" in s),
    }


# ---- the expert layers' device counters (parallel/moe.py makes them) ----


def zero_moe_counts(cfg) -> dict:
    """The counters a forward adds to, at zero (int32; the engine drains
    them to the host at every ``stats()``)."""
    return {
        "moe_tokens": jnp.zeros((), jnp.int32),
        "moe_local_pairs": jnp.zeros((), jnp.int32),
        "moe_expert_tokens": jnp.zeros((cfg.experts_held[1],), jnp.int32),
        "moe_experts_touched": jnp.zeros((), jnp.int32),
    }


def derived_moe_stats(cfg, n: dict) -> dict:
    """What ``ServingEngine.stats()`` adds from those counters: of the
    experts the tokens selected, the share held here (100 x held / router's
    width where the router really routes over all of them), and the busiest
    held expert's tokens over the mean."""
    per_expert = [int(t) for t in n["moe_expert_tokens"]]
    mean = sum(per_expert) / len(per_expert)
    picks = cfg.top_k * int(n["moe_tokens"])
    return {
        "expert_local_hit_pct": round(100.0 * int(n["moe_local_pairs"]) / picks, 4) if picks else None,
        "expert_load_max_over_mean": round(max(per_expert) / mean, 4) if mean else None,
    }


def check_experts_held(cfg) -> None:
    first, count = cfg.experts_held
    if not (0 <= first and count >= 1 and first + count <= cfg.router_width):
        raise ValueError(
            f"experts_held {cfg.experts_held} outside the router's {cfg.router_width}"
        )
    if not 1 <= cfg.top_k <= cfg.router_width:
        raise ValueError(f"top_k={cfg.top_k} outside [1, {cfg.router_width}]")
