"""Expert-parallel Mixture-of-Experts MLP over the ``ep`` mesh axis.

Reference parity note: absent from the reference (SURVEY.md §2 parallelism
table) — beyond-parity, completing the mesh-axis vocabulary with an
executable ``ep`` path (dp/fsdp/tp/sp/pp are covered elsewhere).

TPU-first design: experts live sharded over ``ep`` (each device owns
``E / ep`` experts' FFN weights) inside one ``shard_map`` program. Routing
is the dense-dispatch formulation: every device runs its local experts
over the full token batch and scales each token's output by its gate
weight for that expert (zero for unrouted tokens), then a single ``psum``
over ``ep`` combines expert contributions. No gather/scatter of tokens,
no capacity factors, no dropped tokens — compute per device scales with
local expert count, and the only collective is one psum riding ICI.
(A capacity-based sparse dispatch trades exactness for FLOPs; this layer
prioritizes exactness and XLA-friendly static shapes.)
"""

from __future__ import annotations


def _router_topk(params, x, top_k: int):
    """The ONE router: f32 logits, top-k, renormalized softmax. Both
    dispatch formulations consume this, so routing can never diverge
    between the dense path and the sparse path it is A/B'd against.
    Returns (logits [N, E], top_idx [N, K], probs [N, K])."""
    import jax
    import jax.numpy as jnp

    logits = x.astype(jnp.float32) @ params["gate"].astype(jnp.float32)
    top_vals, top_idx = jax.lax.top_k(logits, top_k)
    probs = jax.nn.softmax(top_vals, axis=-1)
    return logits, top_idx, probs


def _gates(params, x, top_k: int):
    """Per-token dense gate weights [N, E]: softmax over the top-k experts
    (renormalized top-k routing), zero elsewhere."""
    import jax.numpy as jnp

    logits, top_idx, probs = _router_topk(params, x, top_k)
    gates = jnp.zeros_like(logits)
    return jnp.put_along_axis(gates, top_idx, probs, axis=-1, inplace=False)


def load_balance_loss(params, x, top_k: int):
    """Switch-Transformer load-balancing auxiliary loss.

    ``E * Σ_e f_e · P_e`` where ``f_e`` is the fraction of (token,
    choice) routings landing on expert e and ``P_e`` the mean FULL-softmax
    router probability for e. Perfectly balanced routing scores 1.0; a
    router collapsed onto one expert scores ~E. Differentiable through
    ``P_e`` (the f_e term is a straight-through count), which is exactly
    the gradient that spreads the router out — without it, top-k training
    (especially capacity-factor sparse dispatch, which DROPS over-capacity
    tokens) collapses onto a few experts.
    """
    import jax
    import jax.numpy as jnp

    logits, top_idx, _ = _router_topk(params, x, top_k)
    E = logits.shape[-1]
    full_probs = jax.nn.softmax(logits, axis=-1)          # [N, E]
    counts = jax.nn.one_hot(top_idx, E, dtype=jnp.float32).sum(axis=(0, 1))
    f = counts / counts.sum()                             # routing fractions
    p = full_probs.mean(axis=0)                           # mean router prob
    return E * jnp.sum(jax.lax.stop_gradient(f) * p)


def _expert_ffn(w_in, w_out, gates, x):
    """Gated gelu FFN over an expert block: [E?, D, F] weights, [N, E?]
    gates → [N, D]. The shared compute of the sharded and dense paths."""
    import jax
    import jax.numpy as jnp

    h = jax.nn.gelu(jnp.einsum("nd,edf->enf", x, w_in))
    y = jnp.einsum("enf,efd->end", h, w_out)
    return jnp.einsum("end,ne->nd", y, gates.astype(y.dtype))


def moe_mlp_reference(params, x, *, top_k: int = 2):
    """Unsharded dense MoE — the single-device reference/fallback."""
    n_exp = params["w_in"].shape[0]
    if not (1 <= top_k <= n_exp):
        raise ValueError(f"top_k={top_k} outside [1, {n_exp}]")
    return _expert_ffn(
        params["w_in"], params["w_out"], _gates(params, x, top_k), x
    )


def _dispatch_tensors(params, x, top_k: int, capacity: int):
    """GShard-style dispatch/combine one-hots for one token group.

    Returns (dispatch [N, E, C] bool-ish, combine [N, E, C] f32): token n
    goes to slot (e, c) of its routed experts, in arrival order per
    expert; tokens beyond an expert's capacity C are DROPPED (their gate
    contribution vanishes — the capacity-factor tradeoff). Routing
    indices carry no gradient (standard); gate probabilities do.
    """
    import jax
    import jax.numpy as jnp

    logits, top_idx, probs = _router_topk(params, x, top_k)
    E = logits.shape[-1]

    counts = jnp.zeros((E,), jnp.int32)
    dispatch = jnp.zeros(logits.shape + (capacity,), jnp.float32)
    combine = jnp.zeros_like(dispatch)
    for k in range(top_k):
        onehot_k = jax.nn.one_hot(top_idx[:, k], E, dtype=jnp.int32)
        # Position of each token within its expert's arrival order.
        pos_in_e = jnp.cumsum(onehot_k, axis=0) - onehot_k + counts[None, :]
        pos_k = (pos_in_e * onehot_k).sum(-1)  # [N]
        counts = counts + onehot_k.sum(0)
        keep = pos_k < capacity
        slot = jax.nn.one_hot(pos_k, capacity, dtype=jnp.float32)
        mask = (
            onehot_k.astype(jnp.float32)[:, :, None]
            * slot[:, None, :]
            * keep.astype(jnp.float32)[:, None, None]
        )
        dispatch = dispatch + mask
        combine = combine + mask * probs[:, k][:, None, None]
    return dispatch, combine


def moe_mlp_sparse(
    params,
    x,
    *,
    top_k: int = 2,
    capacity_factor: float = 1.25,
    group_size: int = 1024,
    mesh=None,
    axis: str = "ep",
):
    """Capacity-factor sparse MoE dispatch (GShard-style einsum form).

    Compute scales with ``top_k * capacity_factor`` instead of with the
    expert count: tokens are grouped (``group_size``), each group routes
    into per-expert capacity ``C = ceil(g * capacity_factor * top_k / E)``
    slots via one-hot dispatch matmuls, the expert FFN runs on the dense
    [groups, E, C, D] buffer, and a combine matmul scatters results back.
    Grouping keeps the dispatch matmul cost linear in N (it is quadratic
    in the group size); the actual group is the largest divisor of N not
    exceeding ``group_size``, so any token count the dense path accepts
    works here too. Tokens beyond an expert's per-group capacity are
    DROPPED — the standard capacity tradeoff; the dense-dispatch path
    (:func:`moe_mlp` / :func:`moe_mlp_reference`) stays the exact option.
    Dense dispatch's cost over the top-k-FLOPs ideal grows with E while
    sparse's stays flat: prefer sparse from E >= 16.

    With ``mesh``: experts shard over ``axis`` (ep) exactly like
    :func:`moe_mlp`; each device computes its local experts' capacity
    block and one psum combines contributions.
    """
    import jax
    import jax.numpy as jnp
    import math as _math

    n_exp, d_model, d_ff = params["w_in"].shape
    if not (1 <= top_k <= n_exp):
        raise ValueError(f"top_k={top_k} outside [1, {n_exp}]")
    N = x.shape[0]
    # Largest divisor of N within group_size: never reject a token count
    # the dense path accepts (a degenerate tiny group just means smaller
    # per-group capacity).
    g = next(d for d in range(min(group_size, N), 0, -1) if N % d == 0)
    capacity = _math.ceil(g * capacity_factor * top_k / n_exp)

    xg = x.reshape(N // g, g, d_model)
    dispatch, combine = jax.vmap(
        lambda xi: _dispatch_tensors(params, xi, top_k, capacity)
    )(xg)

    def ffn(w_in, w_out, dispatch_l, combine_l, xg_l):
        x_e = jnp.einsum("gnec,gnd->gecd", dispatch_l.astype(x.dtype), xg_l)
        h = jax.nn.gelu(jnp.einsum("gecd,edf->gecf", x_e, w_in))
        y = jnp.einsum("gecf,efd->gecd", h, w_out)
        out = jnp.einsum("gnec,gecd->gnd", combine_l.astype(y.dtype), y)
        return out.reshape(N, d_model)

    if mesh is None or axis not in mesh.axis_names or mesh.shape[axis] <= 1:
        return ffn(params["w_in"], params["w_out"], dispatch, combine, xg)

    ep = mesh.shape[axis]
    if n_exp % ep:
        raise ValueError(f"experts {n_exp} not divisible by ep={ep}")
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def per_shard(weights, dispatch_g, combine_g, xg_g):
        w_in, w_out = weights["w_in"], weights["w_out"]
        e_local = w_in.shape[0]
        shard = jax.lax.axis_index(axis)
        d_l = jax.lax.dynamic_slice_in_dim(
            dispatch_g, shard * e_local, e_local, axis=2
        )
        c_l = jax.lax.dynamic_slice_in_dim(
            combine_g, shard * e_local, e_local, axis=2
        )
        return jax.lax.psum(ffn(w_in, w_out, d_l, c_l, xg_g), axis)

    return shard_map(
        per_shard,
        mesh=mesh,
        in_specs=({"w_in": P(axis), "w_out": P(axis)}, P(), P(), P()),
        out_specs=P(),
        axis_names={axis},
    )(
        {"w_in": params["w_in"], "w_out": params["w_out"]},
        dispatch,
        combine,
        xg,
    )


def moe_mlp(
    params,
    x,
    *,
    mesh,
    top_k: int = 2,
    axis: str = "ep",
):
    """Top-k gated MoE feed-forward. x ``[N, D]`` → ``[N, D]``.

    ``params``::

        {"gate": [D, E],                      # router (replicated)
         "w_in": [E, D, F], "w_out": [E, F, D]}  # experts (sharded over ep)

    Gate probabilities are softmax over the top-k experts per token
    (standard renormalized top-k routing); expert FFN is gelu.
    """
    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    n_exp, d_model, d_ff = params["w_in"].shape
    ep = mesh.shape[axis]
    if n_exp % ep:
        raise ValueError(f"experts {n_exp} not divisible by ep={ep}")
    if not (1 <= top_k <= n_exp):
        raise ValueError(f"top_k={top_k} outside [1, {n_exp}]")

    def present(a):
        return a in mesh.axis_names and mesh.shape[a] > 1

    # Compose with the transformer's weight shardings instead of forcing
    # replication (which would silently all-gather the expert weights on
    # every call). Two different semantics for the two axis kinds:
    #  - tp shards the F (mlp) dim Megatron-style WITHIN each expert:
    #    gelu is elementwise over F, so w_in stays column-parallel, w_out
    #    row-parallel, and the output psum below also completes the F
    #    contraction — TP never gathers weights.
    #  - fsdp shards the D (embed) dim as STORAGE only (ZeRO-3): compute
    #    needs full D, so the weights are gathered just-in-time inside the
    #    shard_map (the standard ZeRO gather, explicit here).
    tp_ax = "tp" if present("tp") and d_ff % mesh.shape["tp"] == 0 else None
    fsdp_ax = (
        "fsdp" if present("fsdp") and d_model % mesh.shape["fsdp"] == 0 else None
    )

    # Router runs replicated (it is tiny).
    gates = _gates(params, x, top_k)
    weight_spec = {"w_in": P(axis, fsdp_ax, tp_ax), "w_out": P(axis, tp_ax, fsdp_ax)}
    # Composition with data parallelism: keep tokens sharded over present
    # batch axes (each (dp, ep) device computes its token rows × its local
    # experts) instead of replicating the batch into every ep shard.
    batch_axes = tuple(
        a for a in ("dp", "fsdp") if a in mesh.axis_names and mesh.shape[a] > 1
    )
    n_rows = 1
    for a in batch_axes:
        n_rows *= mesh.shape[a]
    if batch_axes and x.shape[0] % n_rows == 0:
        tok_spec = P(batch_axes)
    else:
        tok_spec = P()

    def per_shard(weights, gates_local, x_local):
        w_in, w_out = weights["w_in"], weights["w_out"]
        if fsdp_ax is not None:
            # ZeRO just-in-time gather of the embed-dim storage shards.
            w_in = jax.lax.all_gather(w_in, fsdp_ax, axis=1, tiled=True)
            w_out = jax.lax.all_gather(w_out, fsdp_ax, axis=2, tiled=True)
        # Local experts: [E/ep, D, F/tp]; this shard's slice of the gate
        # matrix columns.
        e_local = w_in.shape[0]
        shard = jax.lax.axis_index(axis)
        g = jax.lax.dynamic_slice_in_dim(
            gates_local, shard * e_local, e_local, axis=1
        )  # [N_local, E/ep]
        out = _expert_ffn(w_in, w_out, g, x_local)
        # One psum finishes BOTH reductions: expert contributions over ep
        # and (when tp is active) the F contraction over tp.
        return jax.lax.psum(out, (axis,) if tp_ax is None else (axis, tp_ax))

    return shard_map(
        per_shard,
        mesh=mesh,
        in_specs=(weight_spec, tok_spec, tok_spec),
        out_specs=tok_spec,
    )({"w_in": params["w_in"], "w_out": params["w_out"]}, gates, x)


# ---- a chip's share of a sigmoid-routed expert layer ----
#
# What expert parallelism asks of one chip, without its exchange: the
# router keeps its published width (every expert of the layer), the chip
# holds the weights of ``experts_held = (first id, count)`` and computes
# their part of each token's result. Exact: no capacity, no dropped token,
# whatever the imbalance. The parts of all the shares add up to the whole
# layer (tests/test_mimo_v2.py and tests/test_nemotron_h.py pin that against
# the uncut references). An expert is one of two forms: the gated SwiGLU
# ``(silu(x Wg) * (x Wu)) Wd``, or the two-matrix ``relu(x Wu)^2 Wd``.


def route_sigmoid_topk(router, e_bias, x, top_k: int):
    """Sigmoid scores over every expert of the layer, the ``top_k`` picked
    by score + ``e_bias`` (the bias selects and does not weigh), weights
    the picked scores renormalised to sum to one. ``router [D, E]``,
    ``e_bias [E]`` float32, ``x [N, D]``; scores in float32.
    Returns (idx [N, top_k] int32, weights [N, top_k] float32)."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.sigmoid(
        jnp.dot(x, router, preferred_element_type=jnp.float32)
    )
    _, idx = jax.lax.top_k(scores + e_bias.astype(jnp.float32), top_k)
    picked = jnp.take_along_axis(scores, idx, axis=-1)
    return idx, picked / jnp.sum(picked, axis=-1, keepdims=True)


SWIGLU, RELU2 = "swiglu", "relu2"


def moe_held(
    params, x, *, top_k: int, experts_held: tuple[int, int],
    form: str = SWIGLU, weight_scale: float = 1.0,
):
    """This chip's part of the expert layer for ``x [N, D]``: routing over
    all ``router_width`` experts, the sum over the selected experts that
    lie in ``experts_held``; the weights are renormalised over all
    ``top_k`` selected, held or not, then multiplied by ``weight_scale``
    (a model's ``routed_scaling_factor``), and a token none of whose
    experts is held gets zero.

    ``params``: ``router [D, E]``, ``e_bias [E]``, and the held experts'
    ``w_up [n, D, F]``, ``w_down [n, F, D]`` and, for ``form`` SwiGLU,
    ``w_gate [n, D, F]``.

    Every held expert runs over all N tokens with the others' gates at
    zero, and the gates are folded in before the one down-projection that
    contracts over (expert, F). At the sizes the engine calls it with (64
    or 128 rows a decode step, 128 a prefill chunk, 16 experts of 3 x 4096
    x 2048 or 32 of 2 x 2688 x 1856) the layer is bound by reading its
    weights, which this reads once each: PERF.md section 6 (PR 28) has the
    chip's numbers beside a ragged grouped product's.

    Returns ``(y [N, D], counts)``; ``counts`` are int32 sums over this
    call: ``moe_tokens`` (N), ``moe_local_pairs`` (selected experts that
    were held), ``moe_expert_tokens [n]`` (tokens each held expert got) and
    ``moe_experts_touched`` (held experts that got any)."""
    import jax
    import jax.numpy as jnp

    first, n = experts_held
    if form not in (SWIGLU, RELU2):
        raise ValueError(f"expert form {form!r} is not {SWIGLU} or {RELU2}")
    if params["w_up"].shape[0] != n:
        raise ValueError(
            f"experts_held {experts_held} but weights of "
            f"{params['w_up'].shape[0]} experts"
        )
    with jax.named_scope("moe_router"):
        idx, w = route_sigmoid_topk(params["router"], params["e_bias"], x, top_k)
        if weight_scale != 1.0:
            w = w * weight_scale
        local = idx - first  # [N, k]; held where 0 <= local < n
        onehot = local[:, :, None] == jnp.arange(n)[None, None, :]  # [N, k, n]
        gates = jnp.sum(jnp.where(onehot, w[:, :, None], 0.0), axis=1)  # [N, n]
        per_expert = jnp.sum(onehot, axis=(0, 1), dtype=jnp.int32)
        counts = {
            "moe_tokens": jnp.int32(x.shape[0]),
            "moe_local_pairs": jnp.sum(per_expert),
            "moe_expert_tokens": per_expert,
            "moe_experts_touched": jnp.sum(per_expert > 0, dtype=jnp.int32),
        }
    if form == SWIGLU:
        h = jax.nn.silu(
            jnp.einsum("nd,edf->nef", x, params["w_gate"])
        ) * jnp.einsum("nd,edf->nef", x, params["w_up"])
        h = h * gates.astype(h.dtype)[:, :, None]
    else:
        # relu^2 and the gate in float32 between the two products, rounded
        # once to the operand of the second.
        up = jnp.einsum("nd,edf->nef", x, params["w_up"], preferred_element_type=jnp.float32)
        h = (jnp.square(jax.nn.relu(up)) * gates[:, :, None]).astype(x.dtype)
    return jnp.einsum("nef,efd->nd", h, params["w_down"]), counts


def moe_swiglu_held(params, x, *, top_k: int, experts_held: tuple[int, int]):
    """:func:`moe_held` for SwiGLU experts with unscaled weights (the
    ``mimo_v2`` family's layer)."""
    return moe_held(params, x, top_k=top_k, experts_held=experts_held)
