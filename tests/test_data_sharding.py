"""Partitioning rules and data pipeline invariants."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from hypothesis import given, settings, strategies as st

from repro import configs as C
from repro import models
from repro.launch.mesh import make_production_mesh
from repro.parallel import sharding as shd
from repro.data.synthetic import SyntheticLM, DataConfig


def _fake_mesh(shape, names):
    """Abstract mesh stand-in for spec computation (no devices needed)."""
    class FakeMesh:
        axis_names = names
        class devices:
            pass
    m = FakeMesh()
    m.devices = type("D", (), {"shape": shape})()
    return m


def test_spec_for_basic_rules():
    mesh = _fake_mesh((16, 16), ("data", "model"))
    assert shd.spec_for(("embed", "heads"), (4096, 4096), mesh) == \
        P("data", "model")
    assert shd.spec_for(("vocab", None), (128256, 4096), mesh) == P("model")
    assert shd.spec_for(("expert", "embed", "ffn"), (64, 2048, 1024), mesh) \
        == P("data", None, "model")


def test_spec_conflict_resolution():
    """A mesh axis may be claimed once; later claims degrade to None."""
    mesh = _fake_mesh((16, 16), ("data", "model"))
    # both dims map to 'model': first wins
    spec = shd.spec_for(("ffn", "heads"), (1024, 2048), mesh)
    assert spec == P("model")  # trailing None trimmed


def test_spec_divisibility_guard():
    mesh = _fake_mesh((16, 16), ("data", "model"))
    # 20 heads do not divide 16: degrade to replicated
    assert shd.spec_for(("heads",), (20,), mesh) == P()
    assert shd.spec_for(("heads",), (32,), mesh) == P("model")


@settings(max_examples=30, deadline=None)
@given(
    d0=st.sampled_from([1, 8, 20, 64, 256]),
    d1=st.sampled_from([1, 16, 48, 512]),
    axes=st.sampled_from([("embed", "heads"), ("vocab", None),
                          ("ffn", "embed"), (None, None)]),
)
def test_property_spec_always_valid(d0, d1, axes):
    """Any (axes, shape) combination yields a spec with unique mesh axes and
    entries only on dividing dims."""
    mesh = _fake_mesh((16, 16), ("data", "model"))
    spec = shd.spec_for(axes, (d0, d1), mesh)
    used = [e for e in spec if e is not None]
    assert len(used) == len(set(used))
    sizes = {"data": 16, "model": 16}
    for dim, e in zip((d0, d1), list(spec) + [None]):
        if e is not None:
            assert dim % sizes[e] == 0


def test_all_archs_param_specs_on_production_mesh():
    """Every arch's full param tree produces valid NamedShardings on the
    real 16x16 mesh spec system (structure + divisibility)."""
    mesh = _fake_mesh((16, 16), ("data", "model"))
    for arch in C.list_archs():
        cfg = C.get_config(arch)
        axes = models.axes(cfg)
        shapes = jax.eval_shape(
            lambda cfg=cfg: models.init(jax.random.PRNGKey(0), cfg))
        specs = shd.param_specs(axes, shapes, mesh)
        n_sharded = 0
        for sds, spec in zip(
                jax.tree.leaves(shapes),
                jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, P))):
            sizes = {"data": 16, "model": 16}
            for dim, e in zip(sds.shape, list(spec)):
                if e is None:
                    continue
                names = (e,) if isinstance(e, str) else e
                ext = int(np.prod([sizes[n] for n in names]))
                assert dim % ext == 0, (arch, sds.shape, spec)
                n_sharded += 1
        assert n_sharded > 0, arch  # something must actually shard


def test_decode_state_specs_long_context():
    """long_500k: batch=1 cannot shard -> the KV cache sequence dim must
    shard over 'data' (the flash-decode layout)."""
    mesh = _fake_mesh((16, 16), ("data", "model"))
    cfg = C.get_config("zamba2-1.2b")
    shapes = jax.eval_shape(
        lambda: models.init_decode_state(cfg, 1, 524288))
    specs = shd.decode_state_specs(shapes, cfg, mesh)
    kv_spec = specs["kv"].k
    assert "data" in kv_spec  # sequence-sharded
    assert kv_spec[1] is None or kv_spec[1] != "data"  # not on batch


def test_batch_specs():
    mesh = _fake_mesh((2, 16, 16), ("pod", "data", "model"))
    shapes = {"tokens": jax.ShapeDtypeStruct((256, 4096), jnp.int32)}
    spec = shd.batch_specs(shapes, mesh)["tokens"]
    assert spec == P(("pod", "data"), None)
    shapes1 = {"tokens": jax.ShapeDtypeStruct((1, 128), jnp.int32)}
    assert shd.batch_specs(shapes1, mesh)["tokens"] == P(None, None)


@settings(max_examples=15, deadline=None)
@given(step=st.integers(0, 1000), hosts=st.sampled_from([1, 2, 4]))
def test_property_data_determinism_and_partition(step, hosts):
    cfg = DataConfig(vocab_size=512, seq_len=8, global_batch=8)
    src = SyntheticLM(cfg)
    full = [src.batch(step, host_index=h, host_count=hosts)["tokens"]
            for h in range(hosts)]
    again = [src.batch(step, host_index=h, host_count=hosts)["tokens"]
             for h in range(hosts)]
    for a, b in zip(full, again):
        np.testing.assert_array_equal(a, b)
    assert sum(x.shape[0] for x in full) == 8
    # labels are next-token shifted
    b0 = src.batch(step)
    np.testing.assert_array_equal(b0["tokens"][:, 1:], b0["labels"][:, :-1])
