"""The benchmark's counts: bus bytes of an allreduce, model FLOPs."""
import jax
import jax.numpy as jnp
import pytest

from bench import flops, wire


@pytest.mark.parametrize("p", [1, 2, 4, 8])
def test_bus_bytes_match_the_ring_count(p):
    from repro.core.collectives import wire_bytes_per_rank
    nbytes = 1_680_545_792
    assert wire.allreduce_bus_bytes(nbytes, p) == pytest.approx(
        wire_bytes_per_rank(nbytes, p, algorithm="ring"))


def test_bus_bytes_values():
    assert wire.allreduce_bus_bytes(1000, 4) == 1500.0
    assert wire.allreduce_bus_bytes(1000, 1) == 0.0
    with pytest.raises(ValueError):
        wire.allreduce_bus_bytes(1000, 0)


def _program(d, seq, chunk, vocab, n, hd):
    return dict(name="t", family="ssm", n_layers=1, d_model=d, n_heads=0,
                n_kv_heads=0, d_ff=0, vocab=vocab, ssm_state=n, ssm_expand=2,
                ssm_headdim=hd, ssm_chunk=chunk, ssm_conv=4, norm_eps=1e-6)


@pytest.mark.parametrize("d,seq,chunk,vocab,n,hd", [
    (256, 256, 64, 4096, 32, 32),
    (512, 512, 128, 8192, 64, 64),
])
def test_mamba2_forward_flops_match_the_compiler(d, seq, chunk, vocab, n, hd):
    """One layer (the compiler counts a scan's body once) and one loss
    chunk: the compiler's count of the forward pass lies within 5% above
    ours, which leaves out only elementwise work."""
    from repro.models import get_model
    from repro.models.base import ModelConfig

    p = _program(d, seq, chunk, vocab, n, hd)
    model = get_model(ModelConfig(**p, dtype=jnp.float32))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    b = 2
    tok = jax.ShapeDtypeStruct((b, seq), jnp.int32)
    compiled = jax.jit(lambda pr, bt: model.loss(pr, bt)).lower(
        params, {"tokens": tok, "labels": tok}).compile()
    xla = compiled.cost_analysis()["flops"]
    ours = flops.forward_per_token(p) * b * seq
    assert 1.0 <= xla / ours < 1.05


def test_mamba2_370m_train_flops_per_token():
    """The configuration as run: 2.52 GFLOP per trained token, by hand:
    48 layers of 15,353,856 and a 1024 x 50288 head, three times."""
    from bench import spec
    p = spec.cell("train-1chip.mamba2-370m").config["program"]
    assert flops.train_per_token(p) == 2_519_924_736
    assert flops.train_per_token(p) == 3 * flops.forward_per_token(p)
