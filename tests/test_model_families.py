"""The seam between a model family and the decoder that serves it: each
of the six factories of ``models.hybrid_decoder`` builds its own blocks
and hands them to ``HybridDecoder``, whose constructor takes nothing that
is one layer's.  The flattened parameter paths and shapes of every family,
at its tests' tiny configuration, are the committed list
``family_params.json`` (what the benchmark's ``param_spec`` of each kind
names at the real size: a path that moves breaks every cell of the
family)."""

import inspect
import json
import os

import jax
import pytest

from bigdl_tpu import models
from tests import (test_afmoe, test_hybrid_decoder, test_latent_attention,
                   test_lfm2_moe, test_shared_kv_decoder, test_state_space)

TINY = {"mimo_v2": test_hybrid_decoder, "falcon_h1": test_state_space,
        "sarvam_mla": test_latent_attention,
        "phi4_flash": test_shared_kv_decoder, "lfm2_moe": test_lfm2_moe,
        "afmoe": test_afmoe}
with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "family_params.json")) as f:
    COMMITTED = json.load(f)


@pytest.mark.parametrize("family", sorted(TINY))
def test_a_familys_parameter_paths_and_shapes_are_the_committed_list(family):
    tiny = TINY[family]
    model = jax.eval_shape(
        lambda: getattr(models, family)(tiny.CFG, tiny.MAX_LEN))
    flat = jax.tree_util.tree_flatten_with_path(model)[0]
    got = [[jax.tree_util.keystr(path), list(leaf.shape)]
           for path, leaf in flat]
    assert got == COMMITTED[family]


def test_the_decoders_constructor_takes_nothing_that_is_one_layers():
    args = list(inspect.signature(models.HybridDecoder.__init__).parameters)
    assert args == ["self", "vocab_size", "hidden_size", "blocks", "eps",
                    "max_len", "norm", "tie_head", "embedding_multiplier",
                    "lm_head_multiplier"]
