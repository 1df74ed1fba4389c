"""The plain reference against the program's loss and gradients, at a
tiny size on the CPU, in float32 at the highest matmul precision, for
four layouts: GQA 8:1 with q/k/v bias (as qwen2.5-3b, 16/2 heads) and
4:1 without, one segment of a windowed and a global block, and a dense
segment followed by an expert segment. And the family-blind reference
against the dense-only one it replaced, bit for bit."""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bench import harness, weights
from bench.loader import UniformTokens
from bench.reference.model import Reference
from bench.tests.conftest import FAMILY_KEYS, legacy, tiny_config


BLOCKS = {
    "gqa8_bias": dict(num_attention_heads=8, num_key_value_heads=1,
                      head_dim=8, attention_bias=True),
    "gqa4_nobias": dict(num_attention_heads=8, num_key_value_heads=2,
                        head_dim=8, attention_bias=False),
    "window_global": FAMILY_KEYS["dense_window"],
    "dense_then_moe": FAMILY_KEYS["moe"],
}


@pytest.mark.parametrize("block", sorted(BLOCKS))
def test_reference_matches_program_in_f32(tiny_bench, block):
    from repro.models.api import build_model
    from repro.models.transformer import RunSettings
    conf = tiny_config(torch_dtype="float32", vocab_size=300,
                       **BLOCKS[block])
    fam = harness.family(conf)
    api = build_model(fam.program_config(conf))
    shapes = fam.param_shapes(conf)
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
        got, g_got = Reference(conf, fam.blocks(conf),
                               **blocks).loss_and_grads(params, batch)
    assert abs(got - float(want)) <= 1e-5 * abs(float(want))
    flat_w = jax.tree_util.tree_flatten_with_path(g_want)[0]
    for (path, w), g in zip(flat_w, jax.tree.leaves(g_got)):
        w, g = np.asarray(w), np.asarray(g)
        scale = max(float(np.max(np.abs(w))), 1e-12)
        assert np.max(np.abs(g - w)) <= 2e-4 * scale, \
            (weights.leaf_name(path), np.max(np.abs(g - w)), scale)


@pytest.mark.parametrize("planted", [{}, {"quant": "fp8"},
                                     {"residual_from": (1, 0)}],
                         ids=["plain", "fp8", "residual"])
def test_reference_is_the_dense_only_one_bit_for_bit(planted):
    """In the cells' bfloat16 weights: the same loss and gradients as
    the reference that handled one segment of one block kind, with the
    control and the residual fault planted alike."""
    old = legacy()
    conf = tiny_config(vocab_size=300)
    fam = harness.family(conf)
    shapes = fam.param_shapes(conf)
    assert shapes == old.param_shapes(conf)
    params = weights.make_params(5, shapes, conf["init"])
    batch = UniformTokens(conf["vocab_size"], 5).batch(0, 2, 32)
    blocks = dict(q_block=16, head_rows=24)
    got, g_got = Reference(conf, fam.blocks(conf), **planted,
                           **blocks).loss_and_grads(params, batch)
    want, g_want = old.Reference(conf, **planted,
                                 **blocks).loss_and_grads(params, batch)
    assert got == want
    assert jax.tree.structure(g_got) == jax.tree.structure(g_want)
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("name,registry", [("qwen2.5-3b-4l", "qwen2.5-3b")])
def test_configuration_files_keep_the_programs_widths(name, registry):
    """The configuration as run is the program's published model with
    only the keys in `reduced` changed, and its family maps it to the
    very ModelConfig the dense-only mapping gave."""
    from repro.configs import get_config
    conf = harness.load_config(name)
    want = get_config(registry)
    got = harness.family(conf).program_config(conf)
    assert got == legacy().model_config(conf)
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
    fam = harness.family(conf)
    prog = jax.eval_shape(build_model(fam.program_config(conf)).init,
                          jax.random.key(0))
    ref = fam.param_shapes(conf)
    assert jax.tree.structure(prog) == jax.tree.structure(ref)
    assert jax.tree.leaves(prog) == jax.tree.leaves(ref)
    assert len(fam.blocks(conf)) == len(ref["segments"])


def test_a_family_with_no_module_is_refused_with_the_families_found():
    conf = dict(harness.load_config("qwen2.5-3b-4l"), family="no_such")
    with pytest.raises(ValueError, match=r"no_such.*\['dense'\]"):
        harness.family(conf)
    with pytest.raises(ValueError, match="bad families name"):
        harness.family(dict(conf, family="../dense"))
