"""Helpers shared across model families."""

from __future__ import annotations


def remat_policy(cfg):
    """Resolve ``cfg.remat_policy`` to a jax.checkpoint policy (None =
    save nothing beyond block boundaries, i.e. full remat). Duck-typed:
    any config with a ``remat_policy`` field (LlamaConfig).

    ``"dots"`` saves outputs of batch-dim-free dot_generals — the
    projection and MLP GEMMs — so backward recomputes only the cheap
    elementwise/norm work (and attention, whose score einsums carry
    batch dims; the flash kernel recomputes internally regardless).
    """
    import jax

    if cfg.remat_policy == "full":
        return None
    if cfg.remat_policy == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    raise ValueError(
        f"remat_policy={cfg.remat_policy!r} not in ('full', 'dots')"
    )
