"""Schedule-to-first-step latency probe.

The second north-star metric (BASELINE.json:2) is submit-accepted →
first training step executed. This workload is the minimal honest
version of "a training step": spawn under the real supervisor, bring up
the JAX backend on the device the supervisor assigned, jit ONE tiny
step, execute it, and report the first step through the same status
channel every real workload uses (``rendezvous.report_first_step``).

Kept tiny and fixed-shape on purpose: the jit's cache key must be
stable so a warm resubmit (supervisor-injected compile cache) isolates
the supervisor + process-spawn + backend-init cost from XLA compile
time — the cold/warm split bench.py reports.
"""

from __future__ import annotations

import sys

from ..runtime import rendezvous


def main() -> int:
    import time

    # Phase breakdown (VERDICT r3 Next #5): the supervisor's status
    # timestamps cover submit -> launch; these cover everything after
    # main entry, split at the boundaries that differ cold vs warm —
    # jax import (pre-paid by a standby), device-client creation (which
    # a standby must NOT pre-pay: a process that opens the chip holds it),
    # compile (persistent-cache fetch when warm), first execution.
    t_main = time.time()
    world = rendezvous.initialize_from_env()
    t0 = time.time()
    import jax
    import jax.numpy as jnp

    t_import = time.time()
    jax.devices()  # forces backend/client creation
    t_client = time.time()

    @jax.jit
    def step(x):
        return (x @ x).sum()

    x = jnp.ones((256, 256), jnp.bfloat16)
    compiled = step.lower(x).compile()
    t_compile = time.time()
    float(jax.device_get(compiled(x)))
    t_exec = time.time()
    rendezvous.report_first_step(0)
    rendezvous.report(
        "latency_phases",
        main_entry=t_main,
        rendezvous_s=round(t0 - t_main, 3),
        import_jax_s=round(t_import - t0, 3),
        client_init_s=round(t_client - t_import, 3),
        compile_s=round(t_compile - t_client, 3),
        first_exec_s=round(t_exec - t_compile, 3),
    )
    print(
        f"[latency-probe] rank {world.process_id}/{world.num_processes} "
        f"first step done on {jax.devices()[0].platform}",
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
