"""Dygraph BERT mini-pretraining with AMP autocast + the fused-Adam
two-program step — a miniature of bench.py's headline config.

Run: python examples/bert_dygraph.py        (~60s on CPU)
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from paddle_tpu.dygraph import base as dybase
from paddle_tpu.dygraph.functional import functional_loss
from paddle_tpu.models.bert import BertForPretraining
from paddle_tpu.optimizer.fused import make_fused_adam


def main():
    vocab, hidden, layers, heads, ffn, seq, batch = \
        1000, 128, 2, 4, 512, 64, 8

    dybase.enable_dygraph()
    tracer = dybase._dygraph_tracer()
    tracer._amp_enabled = True          # bf16 matmuls on the MXU
    model = BertForPretraining(vocab_size=vocab, hidden_size=hidden,
                               num_layers=layers, num_heads=heads,
                               intermediate_size=ffn, max_position=seq)
    model.train()

    def loss_fn(input_ids, mlm_labels, nsp_labels):
        mlm_logits, nsp_logits = model(input_ids)
        return model.loss(mlm_logits, nsp_logits, mlm_labels, nsp_labels)

    values, lfn = functional_loss(model, loss_fn)
    state, _spec, fused_update = make_fused_adam(values, lr=1e-3)
    jgrad = jax.jit(lambda p, *xs: jax.value_and_grad(lfn)(p, *xs))
    jupdate = jax.jit(fused_update, donate_argnums=(0, 1))
    params = jax.jit(fused_update.params_of)(state)

    rng = np.random.RandomState(0)
    ids = rng.randint(0, vocab, (batch, seq)).astype("int32")
    mlm = rng.randint(0, vocab, (batch, seq)).astype("int32")
    nsp = rng.randint(0, 2, (batch,)).astype("int32")

    for step in range(20):
        loss, grads = jgrad(params, ids, mlm, nsp)
        state, params = jupdate(state, grads)
        if step % 5 == 0:
            print(f"step {step}: loss={float(loss):.4f}")
    print(f"final loss={float(loss):.4f}")


if __name__ == "__main__":
    main()
