"""What a rematted LlamaBlock keeps (`LlamaConfig.remat_policy`): the
default keeps the attention half's residuals by name and reruns the MLP,
"full" keeps the block's input alone. Same loss and gradients under
every policy, the kept set is the named set, the flash forward runs once,
`SpmdStep.remat_saved_residuals` says how many values are kept, and the
serving programs are the ones they were (CPU, four virtual devices where
a mesh is needed; the flash kernel interpreted).
"""
import dataclasses
import functools
import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax._src.ad_checkpoint import saved_residuals

from ray_tpu.models import Llama, LlamaConfig
from ray_tpu.models import llama as llama_mod
from ray_tpu.models.llama import LlamaBlock
from ray_tpu.ops import attention as attention_mod
from ray_tpu.ops import rope_frequencies
from ray_tpu.ops.attention import ATTN_RESIDUALS
from ray_tpu.parallel import MeshSpec, build_mesh
from ray_tpu.train.spmd import make_train_step

# the package exports the function under the module's name
flash_mod = importlib.import_module("ray_tpu.ops.pallas.flash_attention")
KEPT = set(ATTN_RESIDUALS) | {"attn_resid"}
POLICIES = {"default": {"remat": True},
            "full": {"remat": True, "remat_policy": "full"},
            "none": {"remat": False}}


def _cfg(how, **kw):
    return LlamaConfig.debug(dtype=jnp.float32, **POLICIES[how], **kw)


def _tokens(batch=4, seq=32, vocab=256):
    return jnp.asarray(np.random.RandomState(7).randint(
        0, vocab, (batch, seq + 1)), jnp.int32)


def _sgd_step(cfg, spec):
    """One SGD step of a toy Llama through make_train_step: (the update
    to every parameter = -0.1 x its gradient, the metrics, the step)."""
    mesh = build_mesh(spec, devices=jax.devices()[:spec.size])
    init = make_train_step(Llama(cfg), optax.sgd(0.1), mesh)
    tokens = _tokens()
    state, step = init(jax.random.PRNGKey(3), {"tokens": tokens})
    before = jax.device_get(state.params)
    state, metrics = step(state, {"tokens": tokens})
    update = jax.tree.map(np.subtract, jax.device_get(state.params), before)
    return update, {k: float(v) for k, v in metrics.items()}, step


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("spec", [MeshSpec(), MeshSpec(fsdp=2, tp=2)],
                         ids=["one-device", "fsdp2tp2"])
def test_loss_and_gradients_are_the_same_under_every_policy(spec, impl):
    """A kept value is the value the forward computed: loss, gradient
    norm and every leaf's update agree between the default, "full" and
    no remat to float32 rounding of a re-fused elementwise op."""
    runs = {how: _sgd_step(_cfg(how, attn_impl=impl), spec)
            for how in POLICIES}
    ref_update, ref_metrics, _ = runs["none"]
    for how in ("default", "full"):
        update, metrics, _ = runs[how]
        assert metrics["loss"] == pytest.approx(ref_metrics["loss"],
                                                rel=1e-6)
        assert metrics["grad_norm"] == pytest.approx(
            ref_metrics["grad_norm"], rel=1e-5)
        for (path, a), b in zip(
                jax.tree_util.tree_leaves_with_path(update),
                jax.tree_util.tree_leaves(ref_update)):
            assert np.linalg.norm(a - b) <= 1e-5 * np.linalg.norm(b), (
                how, path)


def _block(impl, batch=2, seq=16):
    cfg = LlamaConfig.debug(dtype=jnp.float32, attn_impl=impl)
    block = LlamaBlock(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (batch, seq, cfg.d_model))
    cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq_len,
                                cfg.rope_theta)
    params = block.init(jax.random.PRNGKey(1), x, cos, sin)
    return cfg, (lambda p, x: block.apply(p, x, cos, sin)[0]), params, x


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("policy", ["attention", "full"])
def test_a_rematted_block_saves_the_named_set_and_its_arguments(policy,
                                                                impl):
    """`saved_residuals` of one block under jax.checkpoint: beside the
    block's arguments (and the rotation's constant tables) the default
    keeps q, k, v, the attention's output, the residual stream after
    o_proj and, on the flash kernel's route, the logsumexp; "full" keeps
    nothing."""
    cfg, fn, params, x = _block(impl)
    b, s = x.shape[:2]
    fn = jax.checkpoint(fn, policy=llama_mod._REMAT_POLICIES[policy])
    kept = [(aval, why) for aval, why in saved_residuals(fn, params, x)
            if not why.startswith(("from the argument", "from a constant"))]
    if policy == "full":
        assert not kept, kept
        return
    heads = {"attn_q": cfg.n_heads, "attn_k": cfg.n_kv_heads,
             "attn_v": cfg.n_kv_heads, "attn_out": cfg.n_heads}
    if impl == "pallas":    # the kernel's operands: heads before rows
        want = {n: (b, h, s, cfg.head_dim) for n, h in heads.items()}
        want["attn_lse"] = (b, cfg.n_heads, s)
    else:
        want = {n: (b, s, h, cfg.head_dim) for n, h in heads.items()}
    want["attn_resid"] = (b, s, cfg.d_model)
    assert sorted(a.shape for a, _ in kept) == sorted(want.values())
    # a kept value that the forward goes on to use is listed under the
    # reduce_precision jax.checkpoint puts behind it, not under its name
    named = {m.group(1): a.shape for a, why in kept
             for m in [re.match(r"named '(\w+)'", why)] if m}
    assert set(named) <= KEPT and (named or impl == "xla")
    assert all(want[n] == shape for n, shape in named.items())
    others = [why for _, why in kept if not why.startswith("named")]
    assert all("reduce_precision" in why for why in others), others


@pytest.mark.parametrize("policy,calls", [("attention", 2), ("full", 3)])
def test_the_backward_of_a_block_runs_the_flash_forward_only_under_full(
        policy, calls):
    """The backward's jaxpr of one block on the flash kernel's route
    holds the dQ and the dK/dV kernel, and under "full" the forward
    kernel a second time."""
    _, fn, params, x = _block("pallas")
    fn = jax.checkpoint(fn, policy=llama_mod._REMAT_POLICIES[policy])
    out, vjp = jax.vjp(fn, params, x)
    assert str(jax.make_jaxpr(vjp)(out)).count("pallas_call") == calls


@pytest.mark.parametrize("how,impl,a_layer", [
    ("default", "pallas", 6), ("default", "xla", 5),
    ("full", "pallas", 0), ("none", "pallas", 0)])
@pytest.mark.parametrize("spec", [MeshSpec(), MeshSpec(fsdp=2, tp=2)],
                         ids=["one-device", "fsdp2tp2"])
def test_the_step_counts_the_values_its_blocks_keep(spec, how, impl,
                                                    a_layer):
    cfg = _cfg(how, attn_impl=impl)
    mesh = build_mesh(spec, devices=jax.devices()[:spec.size])
    init = make_train_step(Llama(cfg), optax.sgd(0.1), mesh)
    tokens = _tokens()
    state, step = init(jax.random.PRNGKey(3), {"tokens": tokens})
    assert step.remat_saved_residuals is None       # not traced yet
    step.step_fn.lower(state, {"tokens": tokens})
    assert step.remat_saved_residuals == a_layer * cfg.n_layers


def _serve_call(cfg, params, tokens):
    model = Llama(cfg)
    b, s = tokens.shape
    cache = model.empty_cache(b, 2 * s, dtype=jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    fn = jax.jit(lambda p, t, c, pos: model.apply(
        {"params": p}, t, cache=c, positions=pos))
    text = fn.lower(params, tokens, cache, positions).as_text()
    return text, fn(params, tokens, cache, positions)[0]


def test_the_serving_path_is_the_program_it_was(monkeypatch):
    """`model.apply(..., cache=...)` never remats, whatever the config
    says, and a name outside jax.checkpoint is an identity: the traced
    program holds `name` equations and lowers to the text it lowers to
    with every checkpoint_name taken out, logits bit-equal."""
    tokens = _tokens(2, 15)
    params = Llama(_cfg("none")).init_params(jax.random.PRNGKey(0))
    text, logits = _serve_call(_cfg("none"), params, tokens)
    for how in ("default", "full"):
        text_r, logits_r = _serve_call(_cfg(how), params, tokens)
        assert text_r == text
        np.testing.assert_array_equal(logits_r, logits)
    cfg = _cfg("default")
    jaxpr = str(jax.make_jaxpr(lambda p, t: Llama(cfg).apply(
        {"params": p}, t, cache=Llama(cfg).empty_cache(2, 32),
        positions=jnp.zeros(t.shape, jnp.int32)))(params, tokens))
    assert "name[name=attn_resid]" in jaxpr
    assert "checkpoint" not in jaxpr and "remat" not in jaxpr
    # a fresh paged prefill attends through multi_head_attention, in
    # every family the engine serves
    q = jax.random.normal(jax.random.PRNGKey(2), (2, 16, 4, 16))
    attend = {impl: jax.jit(functools.partial(
        attention_mod.multi_head_attention, impl=impl))
        for impl in ("xla", "pallas")}
    named = {impl: (fn.lower(q, q[:, :, :2], q[:, :, :2]).as_text(),
                    fn(q, q[:, :, :2], q[:, :, :2]))
             for impl, fn in attend.items()}
    for mod in (llama_mod, attention_mod, flash_mod):
        monkeypatch.setattr(mod, "checkpoint_name", lambda x, name: x)
    bare, logits_bare = _serve_call(_cfg("default"), params, tokens)
    assert bare == text
    np.testing.assert_array_equal(logits_bare, logits)
    for impl in attend:
        fn = jax.jit(functools.partial(attention_mod.multi_head_attention,
                                       impl=impl))
        assert fn.lower(q, q[:, :, :2], q[:, :, :2]).as_text() \
            == named[impl][0]
        np.testing.assert_array_equal(fn(q, q[:, :, :2], q[:, :, :2]),
                                      named[impl][1])


def test_an_unknown_policy_still_raises():
    with pytest.raises(ValueError, match="remat_policy"):
        LlamaConfig.debug(remat=True, remat_policy="everything")
    assert dataclasses.replace(
        LlamaConfig.debug(), remat_policy="dots").remat_policy == "dots"
    assert LlamaConfig.debug().remat_policy == "attention"
