"""Host-side (single-device, no subprocess) tests for the collective-
algorithm registry: policy-table semantics, JSON round-trip, override
validation, tuner policy construction, the ParamSharder collective plan,
View truncation scatter, and the JAX-compat shims."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core as jmpi
from repro.core import compat, registry
from repro.core.registry import PolicyRule, PolicyTable


def test_every_op_has_at_least_two_algorithms():
    for op in registry.OPS:
        names = registry.algorithms(op)
        assert registry.DEFAULT_ALGORITHM in names, op
        assert len(names) >= 2, f"{op} needs >=2 interchangeable lowerings: {names}"


def test_policy_rule_matching_and_defaults():
    table = PolicyTable(
        rules=[PolicyRule("allreduce", "recursive_doubling", max_bytes=1024),
               PolicyRule("allreduce", "ring", min_bytes=1 << 20),
               PolicyRule("alltoall", "pairwise", ranks=8)],
        default={"allreduce": "xla_native"})
    assert table.choose("allreduce", 100, 8) == "recursive_doubling"
    assert table.choose("allreduce", 4096, 8) == "xla_native"
    assert table.choose("allreduce", 2 << 20, 8) == "ring"
    assert table.choose("alltoall", 100, 8) == "pairwise"
    assert table.choose("alltoall", 100, 4) == "xla_native"  # ranks pinned
    assert table.choose("bcast", 100, 8) == "xla_native"     # global default


def test_policy_json_roundtrip(tmp_path):
    table = PolicyTable(
        rules=[PolicyRule("bcast", "tree", min_bytes=0, max_bytes=512,
                          ranks=8)],
        default={op: "xla_native" for op in registry.OPS})
    path = tmp_path / "policy.json"
    table.save(str(path))
    loaded = PolicyTable.load(str(path))
    assert loaded == table
    doc = json.loads(path.read_text())
    assert doc["version"] == 1 and doc["rules"][0]["algorithm"] == "tree"
    # load_policy installs it as the active table
    prev = registry.active_policy()
    try:
        active = jmpi.load_policy(str(path))
        assert registry.active_policy() is active
        assert registry.choose_name("bcast", 256, 8) == "tree"
    finally:
        registry.set_policy(prev)


def test_set_algorithm_validates_and_overrides():
    with pytest.raises(ValueError, match="no algorithm"):
        jmpi.set_algorithm("allreduce", "nope")
    with pytest.raises(ValueError, match="unknown collective op"):
        registry.register("not_an_op", "x")(lambda *a, **k: None)
    try:
        jmpi.set_algorithm("allreduce", "ring")
        assert registry.choose_name("allreduce", 1 << 20, 8) == "ring"
    finally:
        jmpi.clear_algorithms()
    assert registry.choose_name("allreduce", 1 << 20, 8) == "xla_native"
    with jmpi.algorithm_override(bcast="tree"):
        assert registry.choose_name("bcast", 1 << 20, 8) == "tree"
    assert registry.choose_name("bcast", 1 << 20, 8) == "xla_native"


def test_default_policy_is_size_aware():
    # built-in table: latency-bound payloads take the log-round schedules
    assert registry.choose_name("allreduce", 64, 8) == "recursive_doubling"
    assert registry.choose_name("allreduce", 1 << 20, 8) == "xla_native"
    assert registry.choose_name("bcast", 64, 8) == "tree"


def test_tuner_build_policy_from_records():
    from repro.launch.collective_tuner import build_policy, crossover_report

    records = [
        {"op": "allreduce", "algorithm": "xla_native", "numel": 64,
         "nbytes": 256, "ranks": 8, "us_per_call": 10.0},
        {"op": "allreduce", "algorithm": "recursive_doubling", "numel": 64,
         "nbytes": 256, "ranks": 8, "us_per_call": 5.0},
        {"op": "allreduce", "algorithm": "xla_native", "numel": 1024,
         "nbytes": 4096, "ranks": 8, "us_per_call": 12.0},
        {"op": "allreduce", "algorithm": "recursive_doubling", "numel": 1024,
         "nbytes": 4096, "ranks": 8, "us_per_call": 40.0},
    ]
    table = build_policy(records)
    # small regime: rd wins, bounded by the geometric midpoint edge
    assert table.choose("allreduce", 256, 8) == "recursive_doubling"
    assert table.choose("allreduce", 4096, 8) == "xla_native"
    report = crossover_report(records)
    assert "recursive_doubling" in report and "2.00x" in report


class _FakeComm:
    """select() only reads ``backend`` and ``size()`` on the explicit-name
    path, so the message pins below run host-side without a mesh."""

    backend = "emulated"

    def size(self):
        return 8


def test_compressed_lowerings_reject_non_float_payloads():
    """ISSUE 8 satellite pin: ``bf16_wire`` and both EF lowerings refuse
    integer/bool payloads (silent rounding through a quantized wire would
    corrupt them) with the registry's uniform trace-time message — exact
    text pinned here, backend-portable behavior in cases_compression."""
    comm = _FakeComm()
    for name in ("bf16_wire", "int8_ef", "topk_ef"):
        for bad in (jnp.zeros((8,), jnp.int32), jnp.zeros((8,), jnp.bool_)):
            with pytest.raises(
                    ValueError,
                    match=rf"algorithm '{name}' cannot handle this "
                          rf"allreduce call \(shape=\(8,\), "
                          rf"dtype={np.dtype(bad.dtype).name}"):
                registry.select("allreduce", bad, comm, algorithm=name)
        # float payloads select the named lowering
        algo = registry.select("allreduce", jnp.zeros((8,), jnp.float32),
                               comm, algorithm=name,
                               op=jmpi.Operator.SUM)
        assert algo.name == name


def test_compressed_lowerings_reject_non_sum_operators():
    """EF quantization only commutes with SUM — MAX/PROD must raise the
    uniform (algorithm, Operator) error, never silently mis-reduce."""
    comm = _FakeComm()
    x = jnp.zeros((8,), jnp.float32)
    for name in ("int8_ef", "topk_ef"):
        with pytest.raises(ValueError,
                           match=rf"algorithm '{name}' for 'allreduce' does "
                                 rf"not support Operator\.MAX"):
            registry.select("allreduce", x, comm, algorithm=name,
                            op=jmpi.Operator.MAX)


def test_wire_bytes_model_counts_topk_index_bytes():
    """Satellite-4 fix: the top-k wire model charges 8 bytes per kept entry
    (int32 index + fp32 value), with the k >= 1 floor."""
    comp, base = jmpi.wire_bytes_per_rank(4096, 8, topk_frac=1 / 64)
    assert comp == 7 * (4096 // 64) * (4 + 4)
    assert base == 2 * (7 / 8) * 4096 * 4
    tiny, _ = jmpi.wire_bytes_per_rank(16, 8, topk_frac=0.001)
    assert tiny == 7 * 1 * (4 + 4)


def test_param_sharder_collective_plan():
    from repro.distributed.params import ParamSharder
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(1, axes=("data",))
    sharder = ParamSharder(cfg=None, mesh=mesh)
    tree = {"w": jax.ShapeDtypeStruct((1024, 1024), jnp.float32),
            "b": jax.ShapeDtypeStruct((8,), jnp.float32)}
    plan = sharder.collective_plan(tree)
    assert plan["w"]["bytes"] == 1024 * 1024 * 4
    assert plan["b"]["bytes"] == 32
    assert plan["w"]["op"] == plan["b"]["op"] == "allreduce"
    # per-payload routing: the tiny leaf takes the latency algorithm under
    # the built-in policy, the big one stays native
    assert plan["b"]["algorithm"] == registry.choose_name("allreduce", 32, 1)
    assert plan["w"]["algorithm"] == registry.choose_name(
        "allreduce", 1024 * 1024 * 4, 1)
    # bucketed counterpart (ISSUE 5): ONE pytree datatype for the tree —
    # a single wire payload whose size is the sum of the leaves
    bucket = sharder.pytree_plan(tree)
    assert bucket["op"] == "allreduce" and bucket["datatype"] == "pytree"
    assert bucket["leaves"] == 2
    assert bucket["count"] == 1024 * 1024 + 8
    assert bucket["bytes"] == (1024 * 1024 + 8) * 4
    assert bucket["algorithm"] == registry.choose_name(
        "allreduce", bucket["bytes"], 1)


def test_view_scatter_into_truncation_semantics():
    base = jnp.full((3, 4), -1.0, jnp.float32)
    view = jmpi.View(base, (slice(0, 3), slice(0, 4)))
    # longer message: leading elements land, tail dropped
    msg = jnp.arange(20.0, dtype=jnp.float32)
    out = np.asarray(view.scatter_into(msg))
    np.testing.assert_array_equal(out.ravel(), np.arange(12.0))
    # shorter message: untouched slots keep prior contents
    out = np.asarray(view.scatter_into(jnp.arange(5.0, dtype=jnp.float32)))
    np.testing.assert_array_equal(out.ravel()[:5], np.arange(5.0))
    np.testing.assert_array_equal(out.ravel()[5:], -1.0)


def test_compat_shims_single_device():
    mesh = compat.make_mesh((1,), ("ranks",))
    from jax.sharding import PartitionSpec as P

    f = compat.shard_map(lambda x: x * 2, mesh, in_specs=P(), out_specs=P())
    np.testing.assert_array_equal(np.asarray(jax.jit(f)(jnp.ones(3))),
                                  2 * np.ones(3))


def test_property_testing_shim_reports_falsifying_example():
    from repro.testing import _Strategies, _shim_given, _shim_settings

    st = _Strategies

    @_shim_settings(max_examples=50)
    @_shim_given(x=st.integers(0, 100))
    def failing(x):
        assert x < 90, "too big"

    with pytest.raises(AssertionError, match="falsified"):
        failing()

    @_shim_settings(max_examples=10)
    @_shim_given(a=st.sampled_from([1, 2]), b=st.tuples(st.booleans()))
    def passing(a, b):
        assert a in (1, 2) and isinstance(b[0], bool)

    passing()


_PROPERTY_DRAWS = r"""
import json
from repro.testing import property_testing

given, settings, st = property_testing()
draws = []


@settings(max_examples=12, deadline=None)
@given(x=st.integers(0, 2 ** 16), dt=st.sampled_from(["f32", "f64", "i32"]))
def inner(x, dt):
    draws.append([x, dt])


inner()
print(json.dumps({"engine": given.__module__, "draws": draws}))
"""


@pytest.mark.parametrize("backend", ["multiproc", None])
def test_property_testing_draws_repeat_in_fresh_processes(backend, tmp_path):
    """Two fresh processes draw the same property examples.  Under
    ``JMPI_BACKEND=multiproc`` (every rank a process of its own) they are
    the seeded shim's; otherwise hypothesis, derandomized and with no
    example database (``.hypothesis/examples``)."""
    import importlib.util
    import subprocess
    import sys

    from repro.testing import child_env

    env = child_env(1)
    env.pop("JMPI_BACKEND", None)
    if backend is not None:
        env["JMPI_BACKEND"] = backend
    runs = [json.loads(subprocess.run(
        [sys.executable, "-c", _PROPERTY_DRAWS], env=env, cwd=tmp_path,
        capture_output=True, text=True, timeout=120, check=True).stdout)
        for _ in range(2)]
    assert runs[0] == runs[1]
    assert len(runs[0]["draws"]) == 12
    assert not (tmp_path / ".hypothesis" / "examples").exists()
    if backend == "multiproc":
        from repro.testing import _Strategies, _shim_given, _shim_settings

        st, shim_draws = _Strategies, []

        @_shim_settings(max_examples=12)
        @_shim_given(x=st.integers(0, 2 ** 16),
                     dt=st.sampled_from(["f32", "f64", "i32"]))
        def shim(x, dt):
            shim_draws.append([x, dt])

        shim()
        assert runs[0] == {"engine": "repro.testing", "draws": shim_draws}
    elif importlib.util.find_spec("hypothesis") is not None:
        assert runs[0]["engine"].startswith("hypothesis")
