"""The plain reference against the program's loss and gradients, at a
tiny size on the CPU, in float32 at the highest matmul precision, for
two block shapes: GQA 8:1 with q/k/v bias (as qwen2.5-3b, 16/2 heads)
and 4:1 without."""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bench import harness, weights
from bench.loader import UniformTokens
from bench.reference.model import Reference, param_shapes
from bench.tests.conftest import tiny_config


BLOCKS = {
    "gqa8_bias": dict(num_attention_heads=8, num_key_value_heads=1,
                      head_dim=8, attention_bias=True),
    "gqa4_nobias": dict(num_attention_heads=8, num_key_value_heads=2,
                        head_dim=8, attention_bias=False),
}


@pytest.mark.parametrize("block", sorted(BLOCKS))
def test_reference_matches_program_in_f32(block):
    from repro.models.api import build_model
    from repro.models.transformer import RunSettings
    conf = tiny_config(torch_dtype="float32", vocab_size=300,
                       **BLOCKS[block])
    cfg = harness.model_config(conf)
    api = build_model(cfg)
    shapes = param_shapes(conf)
    assert jax.tree.structure(shapes) == jax.tree.structure(
        jax.eval_shape(api.init, jax.random.key(0)))
    params = weights.make_params(7, shapes, conf["init"])
    batch = UniformTokens(conf["vocab_size"], 7).batch(0, 2, 32)
    # queries in blocks of 16 and the head in blocks of 24 rows exercise
    # the reference's blocking against the program's whole-sequence path
    blocks = dict(q_block=16, head_rows=24)
    with jax.default_matmul_precision("highest"):
        settings = RunSettings(attn_impl="xla", attn_chunk=8,
                               activation_policy="keep",
                               param_dtype="float32")
        (want, _), g_want = jax.value_and_grad(api.loss, has_aux=True)(
            params, {k: jnp.asarray(v) for k, v in batch.items()},
            settings)
        got, g_got = Reference(conf, **blocks).loss_and_grads(params, batch)
    assert abs(got - float(want)) <= 1e-5 * abs(float(want))
    flat_w = jax.tree_util.tree_flatten_with_path(g_want)[0]
    for (path, w), g in zip(flat_w, jax.tree.leaves(g_got)):
        w, g = np.asarray(w), np.asarray(g)
        scale = max(float(np.max(np.abs(w))), 1e-12)
        assert np.max(np.abs(g - w)) <= 2e-4 * scale, \
            (weights.leaf_name(path), np.max(np.abs(g - w)), scale)


@pytest.mark.parametrize("name,registry", [("qwen2.5-3b-4l", "qwen2.5-3b")])
def test_configuration_files_keep_the_programs_widths(name, registry):
    """The configuration as run is the program's published model with
    only the keys in `reduced` changed."""
    from repro.configs import get_config
    conf = harness.load_config(name)
    want = get_config(registry)
    got = harness.model_config(conf)
    changed = {f.name for f in dataclasses.fields(want)
               if getattr(want, f.name) != getattr(got, f.name)}
    allowed = {"name", "num_layers"} | (
        {"tie_embeddings"} if "tie_word_embeddings" in conf["reduced"]
        else set())
    assert changed <= allowed, changed
    assert got.num_layers == conf["reduced"]["num_hidden_layers"][1]


@pytest.mark.parametrize("name", ["qwen2.5-3b-4l"])
def test_reference_layout_is_the_programs(name):
    from repro.models.api import build_model
    conf = harness.load_config(name)
    prog = jax.eval_shape(build_model(harness.model_config(conf)).init,
                          jax.random.key(0))
    ref = param_shapes(conf)
    assert jax.tree.structure(prog) == jax.tree.structure(ref)
    assert jax.tree.leaves(prog) == jax.tree.leaves(ref)
