"""The plain reference follows the program's own model: at a tiny size
in float32 on the CPU, the program's loss on the parameter tree the
benchmark builds equals the reference's, with the head tied (no
``lm_head`` leaf, the embedding's transpose) and untied."""
import dataclasses

import jax
import pytest
from bench_tiny import TINY_PROGRAM

from bench import traffic, weights
from bench.drivers.train import model_config, param_shapes, reference_config
from bench.reference import mamba2 as ref_mamba2


@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
def test_program_loss_equals_the_reference(tied):
    from repro.models import get_model

    config = {"program": dict(TINY_PROGRAM, tie_embeddings=tied),
              "compute_dtype": "float32"}
    model = get_model(model_config(config))
    shapes = param_shapes(model)
    assert ("lm_head" in shapes) != tied
    params = weights.init_params(shapes, weights.key_from_seed(2**36 + 7))
    batch = traffic.token_batch(jax.random.PRNGKey(1), 0, vocab=250,
                                batch=2, seq=128, zipf_s=1.3)
    with jax.default_matmul_precision("highest"):
        prog = model.loss(params, batch)
    ref = ref_mamba2.loss(reference_config(config), params, batch["tokens"],
                          batch["labels"])
    assert float(prog) == pytest.approx(float(ref), rel=1e-4)


def test_tied_tree_leaves_out_only_the_head():
    from repro.models import get_model

    cfg = model_config({"program": TINY_PROGRAM, "compute_dtype": "float32"})
    untied = param_shapes(get_model(cfg))
    tied = param_shapes(get_model(dataclasses.replace(cfg, tie_embeddings=True)))
    assert set(untied) - set(tied) == {"lm_head"}
    assert jax.tree.structure(tied["layers"]) == \
        jax.tree.structure(untied["layers"])
    assert tied["embed"].shape == (TINY_PROGRAM["vocab"], TINY_PROGRAM["d_model"])
