"""The reference draws the weights the harness serves from the seed, and
agrees with the program where both compute in float32; the control does
not."""
import time

import jax
import numpy as np

import harness
import reference
from conftest import tiny_cell

SEED = 2**31 + 99


def test_reference_draws_the_weights_the_harness_serves():
    for tied in (True, False):
        cell = tiny_cell(tie_word_embeddings=tied)
        params = harness.build_engine(cell, SEED).params
        m = reference._dims(cell.model)
        mt = tuple(sorted(m.items()))
        ek, hk, sk = reference._top_keys(SEED)
        emb, head = reference._head_weights(ek, hk, mt)
        V = m["V"]
        np.testing.assert_array_equal(emb, params["embed"][:V])
        want_head = (params["embed"][:V].T if m["tied"]
                     else params["lm_head"][:, :V])
        np.testing.assert_array_equal(head, want_head)
        stack = params["stacks"][0]["b0"]
        for li, key in enumerate(jax.random.split(sk, m["L"])):
            w = reference._layer_weights(key, mt)
            for name in ("wq", "wk", "wv", "wo"):
                np.testing.assert_array_equal(w[name],
                                              stack["attn"][name][li])
            for name in ("wg", "wu", "wd"):
                np.testing.assert_array_equal(w[name],
                                              stack["mlp"][name][li])
        x = reference.norms_and_biases(cell.model, SEED)
        for name in ("bq", "bk", "bv"):
            np.testing.assert_array_equal(x[name], stack["attn"][name])
            assert np.std(np.asarray(x[name], np.float32)) > 0.4
        for name in ("ln1", "ln2"):
            np.testing.assert_array_equal(x[name], stack[name]["scale"])
        np.testing.assert_array_equal(x["final"],
                                      params["final_norm"]["scale"])
        assert np.std(np.asarray(x["final"], np.float32)) > 0.1


def test_float32_program_serves_the_references_greedy_tokens(cpu_devices):
    cell = tiny_cell(torch_dtype="float32")
    with jax.default_matmul_precision("highest"):
        run, _, _ = harness.serve_window(cell, SEED, 1.0, cpu_devices[:1],
                                         time.perf_counter())
    c = harness.check(cell, SEED, run.served, control=True)
    assert c["tokens_checked"] > 20
    assert c["max_logit_gap"] < 1e-4
    assert c["control_max_logit_gap"] > 100 * max(c["max_logit_gap"], 1e-6)

