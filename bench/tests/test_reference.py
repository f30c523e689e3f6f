"""The plain float32 reference, looked up from each configuration file,
against the program at smoke size: prefill and cached decode logits, the
training loss and its gradients; and the lookup itself."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import flops, program
from bench.reference import dense_gqa
from bench.tests import smoke

SEED = 2**33 + 7


def rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.fixture(scope="module", params=["h2o-danube-1.8b", "yi-6b-pp4"])
def setup(request):
    config = smoke.config(request.param)
    cfg = program.model_config(config)
    model = config["model"]
    ref = program.reference(config)
    w = ref.init_weights(model, SEED)
    return ref, cfg, model, w, ref.to_program_params(w, cfg), config


def test_layout_matches_the_program(setup):
    _, cfg, _, _, _, config = setup
    program.check_layout(cfg, config)


@pytest.mark.parametrize("name", ["h2o-danube-1.8b", "yi-6b-pp4"])
def test_the_looked_up_module_counts_and_makes_weights_as_dense_gqa(name):
    """Both configurations name ``dense_gqa``: its counts are
    ``bench/lib/flops.py``'s at full widths, and the harness's seeded
    weights are the module's own, bit for bit."""
    config = smoke.load("bench", "configs", f"{name}.json")
    ref, model = program.reference(config), config["model"]
    assert ref is dense_gqa
    assert ref.param_count(model) == flops.param_count(model)
    for start, stop in ((0, 512), (700, 733)):
        assert ref.forward_flops(model, start, stop) == flops.forward_flops(model, start, stop)
    pos = [17 + 293 * i for i in range(16)]
    assert ref.decode_step_flops(model, pos) == flops.decode_step_flops(model, pos)
    assert ref.decode_step_bytes(model, pos, 2, 2) == flops.decode_step_bytes(model, pos, 2, 2)
    assert ref.train_flops_per_token(model, 512) == flops.train_flops_per_token(model, 512)

    small = smoke.config(name)
    cfg = program.model_config(small)
    got = program.seeded_init(small, cfg)(ref.seed_key(SEED))
    want = dense_gqa.to_program_params(dense_gqa.init_weights(small["model"], SEED), cfg)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_prefill_then_cached_decode_match_the_reference(setup):
    from repro.models import transformer as T

    ref, cfg, model, w, params, _ = setup
    toks = np.random.default_rng(0).integers(0, model["vocab_size"], 40).astype(np.int32)
    prompt = 24
    logits, cache = T.prefill(params, cfg, {"tokens": jnp.asarray(toks[None, :prompt])}, 64)
    got = [np.asarray(logits[0])]
    for p in range(prompt, toks.size):
        lg, cache = T.decode_step(params, cfg, jnp.asarray(toks[p : p + 1]), jnp.int32(p), cache)
        got.append(np.asarray(lg[0]))
    padded = np.zeros(64, np.int32)
    padded[: toks.size] = toks
    want = np.asarray(ref.logits_at(w, model, padded, prompt - 1, len(got)))
    # the program multiplies in bfloat16 (8 bits of mantissa): about 1% apart
    for i, g in enumerate(got):
        assert rel(g, want[i]) < 0.03, i


def test_the_float8_control_is_further_off_than_the_program(setup):
    from repro.models import transformer as T

    ref, cfg, model, w, params, _ = setup
    toks = np.random.default_rng(1).integers(0, model["vocab_size"], 64).astype(np.int32)
    logits, _ = T.prefill(params, cfg, {"tokens": jnp.asarray(toks[None])}, 64)
    want = np.asarray(ref.logits_at(w, model, toks, 63, 1))[0]
    low = np.asarray(ref.logits_at(w, model, toks, 63, 1, "fp8"))[0]
    assert rel(low, want) > 3 * rel(np.asarray(logits[0]), want)


def test_loss_and_gradients_match_the_reference(setup):
    from repro.models import transformer as T

    ref, cfg, model, w, params, _ = setup
    rng = np.random.default_rng(2)
    toks = rng.integers(0, model["vocab_size"], (2, 33)).astype(np.int32)
    batch = {"tokens": jnp.asarray(toks[:, :-1]), "labels": jnp.asarray(toks[:, 1:])}
    (loss, _), grads = jax.value_and_grad(
        lambda p: T.loss_fn(p, cfg, batch, remat="none"), has_aux=True)(params)
    ref_loss, ref_grads = jax.value_and_grad(
        lambda p: ref.loss(p, model, batch["tokens"], batch["labels"], 1e-4))(w)
    assert abs(float(loss) - float(ref_loss)) / float(ref_loss) < 1e-3
    ref_grads = ref.to_program_params(ref_grads, cfg)
    for g, r in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_grads)):
        assert rel(g, r) < 0.05


def test_one_step_of_adamw_matches_the_program_optimizer(setup):
    from repro.optim import adamw

    ref, cfg, model, w, params, _ = setup
    opt = {**{f: getattr(adamw.OptConfig(), f) for f in (
        "peak_lr", "warmup_steps", "decay_steps", "min_lr_ratio", "b1", "b2", "eps",
        "weight_decay", "grad_clip")}, "z_loss": 1e-4}
    rng = np.random.default_rng(3)
    grads = jax.tree.map(lambda x: jnp.asarray(rng.normal(size=x.shape), jnp.float32), w)
    new_p, state, _ = adamw.update(adamw.OptConfig(), ref.to_program_params(grads, cfg),
                                   adamw.init(params), params)

    def fake_loss(p, *_a, **_k):  # gradient of sum(p * grads) is grads
        return sum(jnp.sum(a * b) for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(grads)))

    orig = ref.loss
    ref.loss = fake_loss
    try:
        zeros = jax.tree.map(jnp.zeros_like, w)
        w1, mu, _, _, _ = ref.train_step(w, zeros, zeros, None, None, 1, model=model, opt=opt)
    finally:
        ref.loss = orig
    for a, b in zip(jax.tree.leaves(new_p), jax.tree.leaves(ref.to_program_params(w1, cfg))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7)
    for a, b in zip(jax.tree.leaves(state["mu"]), jax.tree.leaves(ref.to_program_params(mu, cfg))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7)
