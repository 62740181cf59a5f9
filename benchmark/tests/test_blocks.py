"""The serving check goes through a model a block at a time: a block's
leaves are the one call's, bit for bit, and the blocks in turn compute
what the whole model computes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import weights
from harness.kinds import decoder_lm as kind
from reference import decoder_lm as ref

CFG = {"vocab_size": 300, "hidden_size": 64, "num_hidden_layers": 3,
       "num_attention_heads": 4, "ffn_dim": 128}
# the leaves of an expert layer beside a model's: a router, experts stacked
# [E, out, in], a norm's gain, a bias
STACKED = [(".embedding.weight", (40, 16)), (".blocks[0].norm.weight", (16,)),
           (".blocks[0].moe.router.weight", (8, 16)),
           (".blocks[0].moe.gate_up.weight", (8, 64, 16)),
           (".blocks[0].moe.down.weight", (8, 16, 32)),
           (".blocks[0].moe.down.bias", (16,))]


def bits(a):
    return np.asarray(a.astype(jnp.float32)).tobytes()


@pytest.mark.parametrize("spec,dtype", [
    (kind.param_spec(CFG), jnp.bfloat16), (STACKED, jnp.bfloat16),
    (STACKED, jnp.float32)], ids=["decoder_lm", "stacked-bf16", "stacked-f32"])
@pytest.mark.parametrize("seed", [7, 3_999_999_999])
def test_a_leaf_made_alone_is_the_one_calls_leaf(spec, dtype, seed):
    whole = weights.make(spec, seed, dtype)
    assert [tuple(l.shape) for l in whole] == [tuple(s) for _, s in spec]
    for i in range(len(spec)):
        alone, = weights.make(spec, seed, dtype, only=[i])
        assert alone.dtype == whole[i].dtype and bits(alone) == bits(whole[i])
    pair = weights.make(spec, seed, dtype, only=[3, 1])
    assert bits(pair[0]) == bits(whole[3]) and bits(pair[1]) == bits(whole[1])


def test_a_stack_of_matrices_is_scaled_by_its_last_axis():
    up = weights.make(STACKED, 5, jnp.float32, only=[3])[0]      # fan-in 16
    down = weights.make(STACKED, 5, jnp.float32, only=[4])[0]    # fan-in 32
    assert float(jnp.std(up)) == pytest.approx(16 ** -0.5, rel=0.05)
    assert float(jnp.std(down)) == pytest.approx(32 ** -0.5, rel=0.05)
    with pytest.raises(ValueError):
        weights.make([(".w.weight", (2, 2, 2, 2, 2))], 5, jnp.float32)


def test_layers_share_one_program():
    spec = kind.param_spec(CFG)
    blocks = kind.param_blocks(CFG)
    assert [n for n, _ in blocks] == ["embedding", "blocks[0]", "blocks[1]",
                                      "blocks[2]", "head"]
    # every leaf lies in a block; only the tied embedding lies in two
    seen = [i for _, idx in blocks for i in idx]
    assert sorted(set(seen)) == list(range(len(spec))) \
        and len(seen) == len(spec) + 1
    weights._BUILDERS.clear()
    walked = {}
    for name, params in weights.blocks_float32(spec, blocks, 11, jnp.bfloat16):
        walked.update(params)
        assert all(l.dtype == jnp.float32 for l in params.values())
    assert len(weights._BUILDERS) == 3       # embedding, a layer, the head
    whole = weights.make(spec, 11, jnp.bfloat16)
    for (path, _), leaf in zip(spec, whole):
        assert bits(walked[path]) == bits(leaf)


@pytest.mark.parametrize("in_blocks", [False, True],
                         ids=["whole-scores", "query-blocks"])
def test_the_blocks_in_turn_equal_the_whole_model(monkeypatch, in_blocks):
    """embed, block by block, head: the logits of the whole forward pass
    to 1e-6, with the queries of attention taken whole and in blocks."""
    if in_blocks:
        monkeypatch.setattr(ref, "SCORES_BYTES", 0)
        monkeypatch.setattr(ref, "Q_BLOCK", 32)
    ref._STEPS.clear()
    spec = kind.param_spec(CFG)
    blocks = kind.param_blocks(CFG)
    tokens = jnp.asarray(np.random.default_rng(3).integers(
        1, CFG["vocab_size"] + 1, (1, 128)).astype(np.int32))
    with jax.default_matmul_precision("highest"):
        leaves = weights.make(spec, 11, jnp.bfloat16)
        params = {p: l.astype(jnp.float32) for (p, _), l in zip(spec, leaves)}
        h = ref.hidden_states(params, tokens, CFG, lambda a: a)
        want = ref.logits_of(params, h, lambda a: a)[0]
        x = None
        for b, (_n, part) in enumerate(weights.blocks_float32(
                spec, blocks, 11, jnp.bfloat16)):
            if b == 0:
                x = ref.embed(part, CFG, tokens)
            elif b < len(blocks) - 1:
                x = ref.block(part, CFG, b - 1, x)
            else:
                got = ref.head(part, CFG, x[0])
    ref._STEPS.clear()
    assert got.shape == want.shape == (128, CFG["vocab_size"] + 1)
    np.testing.assert_allclose(np.asarray(got[:, :-1]), np.asarray(want[:, :-1]),
                               atol=1e-6, rtol=1e-6)
    assert float(got[0, -1]) == ref.NEG     # the never-trained extra row


def test_decode_bytes_come_from_the_kind():
    from harness import flops, manifest
    opt = manifest.load_json(manifest.BENCH_DIR + "/configs/opt-1.3b.json")
    assert manifest.load_kind("decoder_lm").decode_step_bytes(opt, 1000, 2, 4) \
        == flops.decode_step_bytes(opt, 1000, 2, 4)
