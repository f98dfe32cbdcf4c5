"""The training entry with the timed path broken underneath: the step
computes its loss and returns its state unchanged."""

import sys

from pytorch_operator_tpu.workloads import trainer

from benchmark import entry_train

make_step = trainer.make_lm_train_step


def stuck(model, tx, mesh, **kw):
    step = make_step(model, tx, mesh, **{**kw, "donate": False})

    def train_step(state, tokens):
        new_state, loss = step(state, tokens)
        # The optimizer's statistics move on; the parameters do not.
        return {"params": state["params"], "opt_state": new_state["opt_state"]}, loss

    import jax

    return jax.jit(train_step)


trainer.make_lm_train_step = stuck

if __name__ == "__main__":
    sys.exit(entry_train.main())
