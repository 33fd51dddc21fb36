"""Ahead-of-time compiles of the simulator's device path for a TPU v5e.

Nothing here runs on a chip: the TPU compiler, which is installed with
JAX, compiles for a described ``v5e:2x2`` topology and refuses what the
chip would refuse — a kernel whose blocks overflow the scoped VMEM, a
tiling the chip cannot lay out, a program larger than device memory.
The shapes are the simulator's own: pn16 ``(546, 17, 546)``, PN(27)
with its dest axis compacted to the 757 points ``(1514, 28, 757)`` and
whole ``(1514, 28, 1514)``, and the paper's Table-5 PN(31)
``(1986, 32, 1986)`` and Slim Fly MMS(27) ``(1458, 41, 1458)``.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and every test worker imports this
file.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

HBM_BYTES = 16 * 10**9          # one TPU v5e chip

STEP_SHAPES = [(546, 17, 546), (1514, 28, 757), (1514, 28, 1514),
               (1986, 32, 1986), (1458, 41, 1458)]
SHAPE_IDS = ["x".join(map(str, s)) for s in STEP_SHAPES]


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler: nothing to compile against
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU compile written to the persistent cache cannot be read back
    # without a chip; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(one_chip, shape, dtype=np.float32):
    import jax
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


@pytest.mark.parametrize("shape", STEP_SHAPES, ids=SHAPE_IDS)
def test_fused_step_update_compiles(one_chip, shape):
    from repro.kernels.sim_step import DEST_TILE, fused_step_update
    n, k, m = shape
    s = lambda *sh: _spec(one_chip, sh)
    args = (s(n, k, m), s(n, k, m), s(n, k, m), s(n, k), s(n, k), s(n, m),
            _spec(one_chip, (-(-m // DEST_TILE),), np.int32))
    compiled = fused_step_update.lower(*args, impl="pallas").compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("shape", STEP_SHAPES, ids=SHAPE_IDS)
def test_fused_decision_compiles(one_chip, shape):
    from repro.kernels.sim_step import DEST_TILE, fused_decision
    n, k, m = shape
    s = lambda *sh: _spec(one_chip, sh)
    args = (s(n, k), s(n, k, m), s(n, m), s(n, m), s(n, m), s(n),
            _spec(one_chip, (-(-m // DEST_TILE),), np.int32))
    compiled = fused_decision.lower(*args, thr=0.0,
                                    impl="pallas").compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("step", ["frontier", "backward"])
def test_mask_gemm_compiles(one_chip, step):
    from repro.kernels.mask_gemm import backward_step, frontier_step
    b, n = 512, 1986                 # a source block of PN(31)
    f = lambda: _spec(one_chip, (b, n))
    adj = _spec(one_chip, (n, n))
    dist = _spec(one_chip, (b, n), np.int32)
    if step == "frontier":
        lowered = frontier_step.lower(f(), adj, dist, f(), 3)
    else:
        lowered = backward_step.lower(f(), adj, dist, f(), f(), 2)
    assert "tpu_custom_call" in lowered.compile().as_text()


@pytest.mark.parametrize("q", [16, 27], ids=["pn16", "pn27_compacted"])
def test_kernel_step_compiles_within_hbm(one_chip, q):
    """The whole jitted kernel step, route tables as arguments, fits one
    chip's memory.  PN(27) runs as in kernel_bench.pn27_ugal: every
    router active (ugal), the dest axis compacted to the 757 points."""
    import jax

    from repro.core import pn_graph
    from repro.sim import SimConfig
    from repro.sim.engine import init_state, pad_slots
    from repro.sim.kernel import kernel_program, laid_slots
    from repro.sim.tables import build_tables

    g = pn_graph(q)
    t = build_tables(g, np.arange(g.n), dtype=np.float32)
    cols = None if q == 16 else np.arange(q * q + q + 1)
    cfg = SimConfig(routing="ugal_threshold(0)")
    jitted, tabs = kernel_program(t, cfg, np.float32, "pallas",
                                  dest_cols=cols)
    # the state as the step lays it out (K rounded up to 8)
    pad = laid_slots(t.k) - t.k
    state = tuple(pad_slots(a, pad) if a.ndim == 3 else a
                  for a in init_state(t, np.float32,
                                      dest_cols=cols).as_tuple())
    c = t.m if cols is None else len(cols)
    inj, cap = np.zeros((t.n, c), np.float32), np.zeros(t.n, np.float32)
    shapes = jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype),
                          (tabs, state, inj, cap))
    compiled = jitted.lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert 0 < total < HBM_BYTES, total


def _moves(hlo: str, shape: str) -> list:
    """Names of the copies and transposes in ``hlo`` that yield ``shape``."""
    import re
    out = []
    for line in hlo.splitlines():
        name, _, rhs = line.strip().partition(" = ")
        op = re.search(r"(?:^| )([a-z][\w-]*)\(", rhs)
        if (op and op.group(1) in ("copy", "copy-start", "transpose")
                and shape in rhs[:op.start()]):
            out.append(name)
    return out


def test_mms27_step_keeps_the_kernels_layout(one_chip):
    """The whole kernel step at the Table-5 Slim Fly MMS(27) (1458
    routers, K = 41, every router a destination), from the shapes alone:
    no (N, K, M)-sized f32 array is copied or relaid around the kernels,
    which the step did while its out-slot axis sat at 41 (three state
    tensors and the split and deliver tables copied, three outputs
    relaid, on every call), and the program fits one chip."""
    import re

    import jax

    from repro.core import mms_graph
    from repro.sim import SimConfig
    from repro.sim.kernel import (_KernelSpec, _kernel_step, kernel_program,
                                  laid_slots)
    from repro.sim.tables import build_tables

    n, k, m = 1458, 41, 1458
    kl = laid_slots(k)
    arcs = n * k
    f32 = lambda *sh: _spec(one_chip, sh)
    i32 = lambda *sh: _spec(one_chip, sh, np.int32)
    full = {"split": f32(n, kl, m), "deliver": f32(n, kl, m),
            "fix_arc": i32(arcs), "fix_dst": i32(arcs),
            "fix_router": i32(arcs), "dst_router": i32(n), "dst_col": i32(n)}
    tabs = {"F": full, "dist_c": f32(n, m), "hval_c": f32(n, m),
            "spread": f32(n, m), "w_val": f32(n, kl), "spread_T": f32(m, n),
            "n_mids": f32(n), "active": i32(m), "head_flat": i32(n * kl),
            "rev": i32(n, kl), "diag_mid": i32(m), "diag_col": i32(m)}
    state = (f32(n, kl, m), f32(n, kl, m), f32(n, kl, m), f32(n, m),
             f32(m, m), f32(m))
    cfg = SimConfig(routing="ugal_threshold(0)")
    # the same tables as the program builds them, at MMS(5)'s size
    t5 = build_tables(mms_graph(5), np.arange(50), dtype=np.float32)
    _, tabs5 = kernel_program(t5, cfg, np.float32, "pallas")
    assert jax.tree.structure(tabs5) == jax.tree.structure(tabs)
    assert ([(a.ndim, a.dtype) for a in jax.tree.leaves(tabs5)]
            == [(a.ndim, a.dtype) for a in jax.tree.leaves(tabs)])
    spec = _KernelSpec(n, kl, (m, m, m), False, cfg.mode, cfg.threshold,
                       1.0, 1e12, False, "float32", "pallas")
    assert kl == 48
    compiled = _kernel_step(spec).lower(tabs, state, f32(n, m),
                                        f32(n)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    big = re.compile(rf"f32\[{n},{kl},{m}\]{{(\d,\d,\d)[:}}]")
    assert set(big.findall(text)) == {"2,1,0"}
    assert _moves(text, f"f32[{n},{kl},{m}]") == []
    # the arrival gather reads the queues: no zero-row-padded copy
    assert f"f32[{n * kl + 1},{m}]" not in text
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert 0 < total < HBM_BYTES, total


PLANE_CASES = [(1986, 32, (1986, 993)), (1986, 32, (1986,)),
               (1458, 41, (1458,)), (1106, 24, (1106,))]


@pytest.mark.parametrize("case", PLANE_CASES,
                         ids=["pn31_points", "pn31", "mms27", "pn23"])
def test_plane_program_compiles_within_hbm(one_chip, case):
    """The program that makes the route tables' dense planes on the
    device, at the cells' sizes: PN(31) with its dest axis also
    compacted to the 993 points, PN(31) and MMS(27) whole at the kernel
    step's laid out-slots, PN(23) at its K = 24.  It compiles
    for the chip and, with its outputs, fits one chip's memory."""
    from repro.sim.kernel import laid_slots
    from repro.sim.tables import _plane_program
    n, k, widths = case
    kl = laid_slots(k)
    i32 = lambda *sh: _spec(one_chip, sh, np.int32)
    args = (i32(n, n), i32(n, kl), _spec(one_chip, (n, kl), np.bool_),
            _spec(one_chip, (n, n)), _spec(one_chip, (k + 1,)),
            tuple(i32(w) for w in widths))
    compiled = _plane_program().lower(*args).compile()
    mem = compiled.memory_analysis()
    planes = sum(2 * 4 * n * kl * w for w in widths)
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert mem.output_size_in_bytes >= planes
    assert 0 < total < HBM_BYTES, total
