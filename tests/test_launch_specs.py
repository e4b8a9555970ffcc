"""Launch-layer logic: bundle building (1x1 mesh — no allocation),
window resolution, FL-replica feasibility, roofline param accounting."""
import jax
import pytest

from repro.configs import get_config
from repro.configs.base import SHAPES
from repro.launch.mesh import make_mesh
from repro.launch.specs import _resolve_window, build_bundle, fl_replica_feasible, param_bytes


@pytest.fixture(scope="module")
def tiny_mesh():
    return make_mesh((1, 1), ("data", "model"))


def test_window_resolution():
    long = SHAPES["long_500k"]
    dense = get_config("granite-8b")
    assert _resolve_window(dense, long) == 4096        # forced window
    ssm = get_config("xlstm-1.3b")
    assert _resolve_window(ssm, long) is None          # natively subquad
    hybrid = get_config("recurrentgemma-2b")
    assert _resolve_window(hybrid, long) is None
    train = SHAPES["train_4k"]
    assert _resolve_window(dense, train) is None


def test_param_bytes_ordering():
    """Param accounting sanity: qwen3 >> granite-8b > stablelm-1.6b."""
    q = param_bytes(get_config("qwen3-moe-235b-a22b"))
    g = param_bytes(get_config("granite-8b"))
    s = param_bytes(get_config("stablelm-1.6b"))
    assert q > 8e11            # ~235B params f32
    assert 2.5e10 < g < 5e10   # ~8B params f32
    assert s < g < q


def test_fl_replica_feasibility(tiny_mesh):
    # budget check is per model-axis shard; with model=1 only tiny archs fit
    assert not fl_replica_feasible(get_config("qwen3-moe-235b-a22b"),
                                   tiny_mesh)
    assert fl_replica_feasible(
        get_config("granite-moe-1b-a400m").reduced(), tiny_mesh)


@pytest.mark.parametrize("arch", ["stablelm-1.6b", "qwen3-moe-235b-a22b",
                                  "xlstm-1.3b", "recurrentgemma-2b",
                                  "seamless-m4t-large-v2",
                                  "llava-next-mistral-7b"])
@pytest.mark.parametrize("shape", ["decode_32k", "long_500k"])
def test_decode_bundles_build_without_allocation(arch, shape, tiny_mesh):
    """ShapeDtypeStruct-only bundle building for the serve shapes (the
    full-config structs; nothing touches device memory)."""
    b = build_bundle(arch, shape, tiny_mesh)
    assert b.kind == "decode"
    leaves = jax.tree.leaves(b.args,
                             is_leaf=lambda x: hasattr(x, "shape"))
    assert all(isinstance(x, jax.ShapeDtypeStruct) for x in leaves
               if hasattr(x, "dtype"))
    # decode token batch has the assigned global batch
    token = b.args[-1]["token"]
    assert token.shape[0] == SHAPES[shape].global_batch
    # ring cache: long_500k attention archs carry a window-sized cache
    if shape == "long_500k" and b.meta.get("window"):
        assert b.meta["cache_len"] == b.meta["window"]
    # in/out shardings mirror the args/output structure
    assert len(b.in_shardings) == len(b.args)


def test_train_bundle_modes(tiny_mesh):
    b = build_bundle("qwen3-moe-235b-a22b", "train_4k", tiny_mesh)
    assert b.mode == "standard"          # 235B replica can never fit
    assert "note" in b.meta


def test_moe_active_params():
    from benchmarks.bench_roofline import model_params
    n_total, n_active = model_params("qwen3-moe-235b-a22b")
    assert n_total > 2e11                # ~235B
    assert n_active < 0.15 * n_total     # a22b: ~22B active
    d_total, d_active = model_params("granite-8b")
    assert d_total == d_active           # dense: all params active
