"""The OPT decode bundle (benchmark/configs/opt-1.3b/program.py) against the
plain float32 reference at a tiny preset: chunked prefill and then decode
through the paged arena give the tokens, and the logits, of the reference's
full forward."""
import os

import numpy as np
import pytest

from benchmark import manifest, weights
from benchmark.references import opt as reference

CFG = {"hidden_size": 64, "ffn_dim": 128, "num_attention_heads": 4,
       "num_hidden_layers": 2, "vocab_size": 512, "max_position_embeddings": 64,
       "init_std": 0.05, "block_size": 4, "max_blocks_per_seq": 16,
       "prefill_chunk_tokens": 8, "step_buckets": [1, 2, 4], "slot_capacity": 4,
       "max_queue": 64, "request_timeout_s": 60}


@pytest.fixture(scope="module")
def program():
    return manifest.load_module(os.path.join(
        os.path.dirname(manifest.__file__), "configs", "opt-1.3b", "program.py"),
        "opt_program_under_test")


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_then_decode_equals_the_full_forward(program, dtype):
    import jax.numpy as jnp
    cfg = dict(CFG, dtype=dtype)
    w = weights.make(11, reference.param_specs(cfg), round_to="bfloat16")
    sess = program.session(cfg, {k: v.astype(dtype) for k, v in w.items()})
    try:
        rng = np.random.RandomState(0)
        for plen in (5, 8, 13, 30):      # under, at and over the chunk of 8
            prompt = [int(x) for x in rng.randint(0, 512, plen)]
            toks = sess.generate(prompt, max_new_tokens=12, timeout=60)["tokens"]
            seq = jnp.asarray([prompt + toks], jnp.int32)
            logits = reference.forward(w, seq[:, :-1], cfg, remat=False)[0][plen - 1:]
            got = logits[jnp.arange(len(toks)), jnp.asarray(toks)]
            gap = float(jnp.max(jnp.max(logits, -1) - got))
            assert len(toks) == 12
            assert gap <= (0.0 if dtype == "float32" else 0.05), (plen, gap)
    finally:
        sess.close()
