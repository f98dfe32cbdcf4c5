"""Token sampling for the decode paths (greedy / temperature / top-k /
nucleus), shared by the single-stream generate workload and the
continuous-batching serving engine.

Reference analog: none (the reference is a training operator). The
TPU-relevant shape choice: top-k and top-p mask off ONE shared
descending sort — the sort is the dominant sampling cost on the decode
hot path, so the knobs compose on a single O(V log V) pass instead of
two.
"""

from __future__ import annotations


def validate_sampling(temperature: float, top_k: int, top_p: float) -> None:
    """The shared front-door checks (ValueError on bad knobs)."""
    if not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p={top_p} not in (0, 1]")
    if top_k < 0:
        raise ValueError(f"top_k={top_k} must be 0 (off) or >= 1")
    if temperature == 0.0 and (top_k > 0 or top_p < 1.0):
        # T=0 short-circuits to argmax; silently ignoring the knobs
        # would hand every row the identical greedy rollout.
        raise ValueError(
            "top_k/top_p require temperature > 0 (temperature=0 is greedy)"
        )


def make_sampler(
    temperature: float = 0.0, top_k: int = 0, top_p: float = 1.0
):
    """Build ``sample(logits [..., V], rng) -> tokens [...] int32``.

    Greedy at T=0, else categorical over the temperature-scaled logits
    with optional top-k and/or nucleus (top-p) truncation — static-shape
    masks off one shared descending sort. Nucleus composes on the
    top-k-truncated distribution (HF-style sequential semantics).
    """
    import jax
    import jax.numpy as jnp

    validate_sampling(temperature, top_k, top_p)

    @jax.named_scope("sample")
    def sample(logits, rng):
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        logits = logits / temperature
        neg = jnp.finfo(logits.dtype).min
        V = logits.shape[-1]
        if (0 < top_k < V) or top_p < 1.0:
            sorted_desc = jnp.sort(logits, axis=-1)[..., ::-1]
            if 0 < top_k < V:
                # Keep the k highest logits: threshold at the k-th value
                # (ties at the threshold survive).
                kth = sorted_desc[..., top_k - 1 : top_k]
                logits = jnp.where(logits < kth, neg, logits)
                sorted_desc = jnp.where(
                    jnp.arange(V) >= top_k, neg, sorted_desc
                )
            if top_p < 1.0:
                # Smallest token set whose cumulative probability
                # reaches top_p; the top token always survives.
                probs = jax.nn.softmax(sorted_desc, axis=-1)
                cum = jnp.cumsum(probs, axis=-1)
                keep = jnp.sum(cum < top_p, axis=-1, keepdims=True)
                # float cumsum can fail to reach a top_p near 1.0 (and
                # saturates early under a composed top_k), making keep
                # == V; the always-keep-top-token invariant must not
                # rest on gather's implicit index clamping.
                keep = jnp.minimum(keep, V - 1)
                cutoff = jnp.take_along_axis(sorted_desc, keep, axis=-1)
                logits = jnp.where(logits < cutoff, neg, logits)
        return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)

    return sample
