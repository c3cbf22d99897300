"""The plain reference: a request run over its own rows, rounded up to a
prompt-row block, gives the logits it gives over every row of the pool."""

import numpy as np
import pytest

from bench.reference import models as R

ARCH = dict(name="t", family="dense", n_layers=2, d_model=128, n_heads=4, n_kv_heads=4,
            head_dim=32, d_ff=256, vocab=512, qkv_bias=False, norm="np_layernorm",
            mlp="swiglu", rope=True, rope_theta=10000.0, tie_embeddings=True, max_seq=128,
            quant={"s_act": 0.05, "s_res": 0.08, "s_w": 0.01})
SERVE = dict(max_len=128, kv_block_size=8, attention_block={"prompt": 16, "generated": 128})


@pytest.mark.parametrize("n_prompt,n_served", [(20, 7), (16, 16), (33, 40), (50, 3)])
def test_reference_over_its_own_rows_equals_the_whole_pool(n_prompt, n_served):
    codes = R.make_codes(ARCH, {"embed_code_std": 2.0}, 2**32 + n_prompt)
    rng = np.random.default_rng(n_prompt)
    prompt, served = rng.integers(0, 512, n_prompt).tolist(), rng.integers(0, 512, n_served).tolist()
    tokens, targets, n, rows = R._request_arrays(ARCH, SERVE, prompt, served)
    assert tokens.shape[1] % 16 == 0 and tokens.shape[1] < 128
    pool = np.zeros((1, 128), np.int32)
    pool_targets = pool.copy()
    pool[:, : tokens.shape[1]], pool_targets[:, : tokens.shape[1]] = tokens, targets
    a, s = R._freeze(ARCH), R._freeze(SERVE)
    own = np.asarray(R._gap(a, s, codes, tokens, n, targets))[rows]
    whole = np.asarray(R._gap(a, s, codes, pool, n, pool_targets))[rows]
    assert np.array_equal(own, whole) and own.max() > 0
    assert np.array_equal(np.asarray(R._argmax(a, s, codes, tokens, n))[0][rows],
                          np.asarray(R._argmax(a, s, codes, pool, n))[0][rows])
