"""Compiles of the fleet programs for a TPU v5e that is described, not
attached.

The TPU compiler is installed with JAX, so the programs of the sweep's
main path can be compiled for the chip on a CPU-only machine: a program
the chip's compiler refuses, or one that does not fit a chip's 16 GB of
HBM, fails here. Nothing runs, so these tests say nothing about results
or times. Shapes are the paper grid's: `PAPER_SSD.scaled(128)`, a 2^16
logical window, full trace lengths.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file. The persistent compilation cache is off around the
compiles, because an entry compiled for a described chip cannot be read
back without one.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.configs.ssd_paper import PAPER_SSD
from repro.core.ssd import fleet
from repro.core.ssd.endurance.spec import EnduranceSpec
from repro.core.ssd.policies import get_spec
from repro.hostcache.spec import HostCacheSpec
from repro.sweep.grid import SweepPoint
from repro.sweep.runner import _cell_params, _endurance_of
from repro.workloads.ir import PAD_OPS

CFG = PAPER_SSD.scaled(128)
N_LOGICAL = 1 << 16
HBM_BYTES = 16 * 10**9          # one v5e chip
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _fleet_args(point: SweepPoint, n_cells: int, t_len: int, sharding, *,
                timeline=None, packed=False):
    """Shapes of one group's (state0, ops, params), each leaf placed
    with `sharding` (built by eval_shape: nothing is allocated)."""
    def build():
        params = fleet.stack_params(
            [_cell_params(CFG, point, 0.0)] * n_cells)
        state0 = fleet.init_fleet_state(
            CFG, N_LOGICAL, n_cells,
            endurance=_endurance_of(point) is not None, timeline=timeline,
            packed=packed, hostcache=point.hostcache)
        return state0, params
    state0, params = jax.eval_shape(build)
    ops = {"arrival_ms": jax.ShapeDtypeStruct((n_cells, t_len), jnp.float32),
           "lba": jax.ShapeDtypeStruct((n_cells, t_len), jnp.int32),
           "is_write": jax.ShapeDtypeStruct((n_cells, t_len), jnp.int32)}

    def place(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)
    return jax.tree.map(place, (state0, ops, params))


def _compile_trim(point, n_cells, t_scan, sharding):
    state0, ops, params = _fleet_args(point, n_cells, t_scan, sharding,
                                      packed=True)
    pad_t = jax.ShapeDtypeStruct((n_cells,), jnp.float32, sharding=sharding)
    return fleet._run_fleet_trim.lower(
        CFG, get_spec(point.policy), state0, ops, params, pad_t,
        closed_loop=point.mode == "bursty", n_pad=PAD_OPS - t_scan
    ).compile()


def _fits_one_chip(compiled):
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert 0 < used < HBM_BYTES, used


# the paper grid's trimmed groups: 11 cells, live prefix as the runner
# trims it for the MSR traces (daily 122880 ops, bursty 106496 of 2^17)
@pytest.mark.parametrize("mode,t_scan", [("daily", 122880),
                                         ("bursty", 106496)])
@pytest.mark.parametrize("policy", ["baseline", "ips", "ips_agc", "coop"])
def test_paper_trim_fleet_compiles(topo, no_persistent_cache, policy, mode,
                                   t_scan):
    one = SingleDeviceSharding(topo.devices[0])
    point = SweepPoint(trace="hm_0", mode=mode, policy=policy)
    _fits_one_chip(_compile_trim(point, 11, t_scan, one))


@pytest.mark.parametrize("group", ["endurance", "hostcache",
                                   "hostcache-wb", "telemetry"])
def test_per_op_fleet_compiles(topo, no_persistent_cache, group):
    one = SingleDeviceSharding(topo.devices[0])
    timeline = None
    if group == "endurance":
        point = SweepPoint(trace="hm_0", mode="bursty", policy="ips_raro",
                           endurance=EnduranceSpec(w_rp=4.0, rp_budget=2.0,
                                                   cycle_budget=15.0))
        n_cells = 3
    elif group == "hostcache":
        point = SweepPoint(trace="flush_burst", mode="daily",
                           policy="coop",
                           hostcache=HostCacheSpec(mode="wb",
                                                   flush="watermark"))
        n_cells = 1
    elif group == "hostcache-wb":
        # the benchmark cell's tier and fleet: dm-writecache's write-only
        # allocation and watermarks, 8 cells
        point = SweepPoint(trace="flush_burst", mode="daily",
                           policy="coop",
                           hostcache=HostCacheSpec(mode="wb",
                                                   promote="write",
                                                   wm_hi=0.5, wm_lo=0.45))
        n_cells = 8
    else:
        point = SweepPoint(trace="hm_0", mode="daily", policy="ips")
        n_cells, timeline = 8, 1024
    state0, ops, params = _fleet_args(point, n_cells, PAD_OPS, one,
                                      timeline=timeline)
    compiled = fleet._run_fleet.lower(
        CFG, get_spec(point.policy), state0, ops, params,
        closed_loop=point.mode == "bursty", timeline_ops=timeline,
        hostcache=point.hostcache).compile()
    _fits_one_chip(compiled)


def test_tier_trim_fleet_compiles(topo, no_persistent_cache):
    """The benchmark cell's trimmed tier fleet: dm-writecache's tier in
    front of coop, 8 cells, the flush_burst live prefix as the runner
    trims it (57,344 of 2^17 steps), the rest replayed."""
    one = SingleDeviceSharding(topo.devices[0])
    hc = HostCacheSpec(mode="wb", promote="write", wm_hi=0.5, wm_lo=0.45)
    point = SweepPoint(trace="flush_burst", mode="daily", policy="coop",
                       hostcache=hc)
    n_cells, t_scan = 8, 57344
    state0, ops, params = _fleet_args(point, n_cells, t_scan, one)
    pad_t = jax.ShapeDtypeStruct((n_cells,), jnp.float32, sharding=one)
    compiled = fleet._run_tier_trim.lower(
        CFG, get_spec(point.policy), state0, ops, params, pad_t,
        closed_loop=False, n_pad=PAD_OPS - t_scan, hostcache=hc).compile()
    _fits_one_chip(compiled)


def _computations(hlo: str) -> dict:
    """{computation name: its instruction lines} of an HLO module text."""
    comps, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)
    return {k: "\n".join(v) for k, v in comps.items()}


def _reachable(comps: dict, roots) -> set:
    """Computations called, directly or not, from `roots`."""
    seen, todo = set(), list(roots)
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        todo += re.findall(r"%([\w.\-]+)", comps[c])
    return seen


def _scan_loop(hlo: str, comps: dict, cells_per_chip: int) -> set:
    """Computations of the fleet scan: the one loop that carries each
    chip's residency maps."""
    scans = [line for line in hlo.splitlines() if " while(" in line
             and f"s8[{cells_per_chip},{N_LOGICAL}]"
             in line.split(" while(")[0]]
    assert len(scans) == 1, scans
    loop = _reachable(comps, re.findall(r"(?:condition|body)=%([\w.\-]+)",
                                        scans[0]))
    assert loop
    return loop


def _indexed_operands(comps: dict, loop) -> list:
    """(op, operand shape) of every scatter and gather in `loop`."""
    found = []
    define = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (\w+\[[\d,]*\])", re.M)
    for c in loop:
        shapes = dict(define.findall(comps[c]))
        for op, arg in re.findall(r" (scatter|gather)\(%([\w.\-]+)",
                                  comps[c]):
            found.append((op, shapes.get(arg)))
    return found


@pytest.mark.parametrize("policy", ["baseline", "ips", "ips_agc", "coop"])
def test_scan_step_has_no_plane_scatter(topo, no_persistent_cache, policy):
    """The step core reads and writes the (C, P) plane carry with masked
    lane ops (policies.engine.lane_get/lane_set/lane_add). Indexed per
    cell instead, each read is a batched gather and each write a batched
    scatter, a serial fusion of ~0.9 us on the chip. Only the residency
    map's gather and scatter may stay in the daily scan."""
    one = SingleDeviceSharding(topo.devices[0])
    point = SweepPoint(trace="hm_0", mode="daily", policy=policy)
    n_cells, planes = 11, CFG.num_planes
    hlo = _compile_trim(point, n_cells, 122880, one).as_text()
    comps = _computations(hlo)
    found = _indexed_operands(comps, _scan_loop(hlo, comps, n_cells))
    assert all(shape for _, shape in found), found
    plane = [(op, shape) for op, shape in found
             if shape.endswith(f"[{n_cells},{planes}]")]
    assert not plane, plane
    scatters = [shape for op, shape in found if op == "scatter"]
    assert len(scatters) <= 2, scatters
    assert all(shape.endswith(f"[{n_cells},{N_LOGICAL}]")
               for shape in scatters), scatters


def test_four_chip_scan_has_no_collectives(topo, no_persistent_cache):
    """Cells are independent, so a fleet laid over four chips along
    ("cells",) must scan with no cross-chip traffic in the loop. (The
    pad-tail replay after the scan is a batched while_loop: its stop
    test, "any cell still changing", is one scalar all-reduce per
    iteration, outside the scan.)"""
    mesh = jax.sharding.Mesh(np.array(topo.devices), ("cells",))
    cells = NamedSharding(mesh, PartitionSpec("cells"))
    point = SweepPoint(trace="hm_0", mode="daily", policy="ips")
    hlo = _compile_trim(point, 12, 122880, cells).as_text()
    comps = _computations(hlo)
    loop = _scan_loop(hlo, comps, 3)     # each chip carries 3 cells' maps
    found = {(c, op) for c in loop for op in COLLECTIVES
             if re.search(rf" {op}(-start)?\(", comps[c])}
    assert not found, found
