"""The plain MiniCPM reference against the program's ``models/lm.py``, at
the smoke size on the CPU: same loss and same gradients from the same
weights and tokens, where the program's table has no padding rows; and the
gap where it has some."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import compare, smoke, weights
from chipbench.archs import minicpm

CONFIG = "minicpm-2b-train.vocab-half"


def _program_config(dm):
    from repro.launch.train import RunConfig, model_config
    return minicpm.configured(model_config, dm)(
        RunConfig(arch="minicpm-2b", use_smoke=True))


def test_reference_matches_program_forward_and_grad():
    from repro.models import lm

    cfg = smoke.config(CONFIG)
    dm = minicpm.dims(cfg)
    mcfg = _program_config(dm)
    assert mcfg.norm_eps == cfg["rms_norm_eps"] == 1e-5
    params = minicpm.program_weights(dm, 2 ** 40 + 3)
    toks = weights.SeededTokens(dm["vocab"], 2, 32, 7, 10.0).tokens(0)
    batch = {"tokens": jnp.asarray(toks), "labels": jnp.asarray(toks)}

    def prog_loss(p):
        return lm.forward(mcfg, p, batch)[0]

    def ref_loss(p):
        return minicpm.loss_fn(dm, minicpm.reference_view(dm, p),
                               jnp.asarray(toks))

    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(prog_loss)(params)
        lr, gr = jax.value_and_grad(ref_loss)(params)
    assert float(lp) == pytest.approx(float(lr), rel=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(gp),
                    jax.tree_util.tree_leaves(gr)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-7)


def test_padded_rows_enter_the_programs_softmax_and_not_the_references():
    """A vocabulary of 500 that the program pads to 512 rows: its loss is
    the loss over all 512 rows, and lies off the reference's loss over the
    500 by more than ``loss_gap``'s limit (the fault that keeps the
    published 122,753-token vocabulary out of the benchmark)."""
    from repro.models import lm

    cfg = smoke.config(CONFIG)
    cfg["vocab_size"] = 500
    dm = minicpm.dims(cfg)
    mcfg = _program_config(dm)
    assert (mcfg.vocab_size, mcfg.padded_vocab) == (500, 512)
    params = minicpm.program_weights(dm, 2 ** 33 + 1)
    toks = jnp.asarray(weights.SeededTokens(500, 2, 32, 5, 10.0).tokens(0))
    w = minicpm.reference_view(dm, params)
    with jax.default_matmul_precision("highest"):
        prog = float(lm.forward(mcfg, params,
                                {"tokens": toks, "labels": toks})[0])
        ref = float(minicpm.loss_fn(dm, w, toks))
        all_rows = float(minicpm.loss_fn(dict(dm, vocab=512), w, toks))
    assert prog == pytest.approx(all_rows, rel=1e-6)
    assert compare.loss_gap([prog], [ref]) > \
        cfg["correct"]["loss_gap"]["limit"] * 10


def test_weights_are_a_function_of_the_whole_seed():
    dm = minicpm.dims(smoke.config(CONFIG))
    a = minicpm.program_weights(dm, 5)["embed"]
    b = minicpm.program_weights(dm, 5 + 2 ** 32)["embed"]
    c = minicpm.program_weights(dm, 5)["embed"]
    assert not np.array_equal(a, b)
    np.testing.assert_array_equal(a, c)


def test_tokens_differ_by_step_and_stay_below_the_vocabulary():
    src = weights.SeededTokens(512, 2, 32, 2 ** 31 + 9, 10.0)
    t0, t1 = src.tokens(0), src.tokens(1)
    assert t0.shape == (2, 32) and t0.dtype == np.int32
    assert not np.array_equal(t0, t1)
    assert not np.array_equal(t0[0], t0[1])
    np.testing.assert_array_equal(t0, src.tokens(0))
    assert 0 <= t0.min() and t1.max() < 512
