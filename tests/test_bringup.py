"""Bring-up contract (ISSUE 21): nothing may let a run pass without the
chip — chip_smoke.py refuses the CPU, a placeable compile cache, a
device store that fails to start stops the start, an unknown device has
no roofline peak, and the normal entry point honours yacy.conf."""

import ast
import inspect
import os
import subprocess
import sys

import pytest

from yacy_search_server_tpu.utils import compilecache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, env_extra=None, timeout=600, cwd=REPO):
    env = dict(os.environ)
    env.update(env_extra or {})
    return subprocess.run([sys.executable, *args], capture_output=True,
                          text=True, timeout=timeout, cwd=cwd, env=env)


# -- chip_smoke.py -----------------------------------------------------------

def test_chip_smoke_refuses_the_cpu():
    r = _run([SMOKE], {"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    # no result line: nothing on stdout parses as the pass object
    assert '"ok"' not in r.stdout


@pytest.mark.parametrize("ndev", [1, 4])
def test_chip_smoke_cpu_rehearsal_runs_every_leg_and_never_passes(ndev):
    """One virtual device rehearses DeviceSegmentStore, four the mesh
    store a four-chip host gets by default (index.device.mesh=auto)."""
    r = _run([SMOKE, "--cpu-rehearsal"], {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={ndev}"})
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    lines = [ln for ln in r.stdout.splitlines() if ln.strip()]
    assert lines and all(ln.startswith("rehearsal ") for ln in lines)
    assert not any(ln.startswith("rehearsal FAIL") for ln in lines)
    assert '{"ok": true' not in r.stdout
    out = r.stdout
    for leg in ("term", "and", "and_not", "site", "hybrid"):
        assert f"rehearsal ok    {leg}: device answers" in out
    if ndev == 1:
        assert "store: DeviceSegmentStore" in out
        for counter in ("join_served +", "stream_scans +",
                        "rerank_queries +", "prewarm_failures == 0"):
            assert counter in out
    else:
        assert "store: MeshSegmentStore" in out
        # a family the store lacks is named, never passed silently
        assert "not on device: site" in out
        assert "not on device: hybrid" in out


# -- compile cache -----------------------------------------------------------

_RESOLVE = ("import jax; "
            "from yacy_search_server_tpu.utils import compilecache as c; "
            "print(c.ensure()); print(jax.config.jax_compilation_cache_dir)")


def test_compile_cache_placed_from_outside_sets_nothing(tmp_path,
                                                        monkeypatch):
    import jax
    placed = str(tmp_path / "cc")
    updates = []
    monkeypatch.setenv(compilecache.ENV_VAR, placed)
    monkeypatch.setattr(jax.config, "update",
                        lambda *a, **kw: updates.append(a))
    assert compilecache.ensure() == placed
    assert updates == []                # no JAX config was touched
    # and JAX reads the variable by itself
    r = _run(["-c", _RESOLVE], {compilecache.ENV_VAR: placed})
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == [placed, placed]


def test_compile_cache_default_is_one_fixed_in_checkout_path():
    env = {"JAX_COMPILATION_CACHE_DIR": "", "PYTHONPATH": REPO}
    a = _run(["-c", _RESOLVE], env)
    b = _run(["-c", _RESOLVE], env, cwd=os.path.dirname(REPO))
    assert a.returncode == 0 and b.returncode == 0, a.stderr + b.stderr
    want = os.path.join(REPO, ".jax_cache")
    assert a.stdout.split() == [want, want]
    assert b.stdout.split() == [want, want]     # same from another cwd/pid
    assert compilecache.DEFAULT_DIR == want


# -- no silent host fallback -------------------------------------------------

def test_switchboard_raises_when_device_store_cannot_start(monkeypatch):
    from yacy_search_server_tpu.index import devstore
    from yacy_search_server_tpu.switchboard import Switchboard
    from yacy_search_server_tpu.utils.config import Config

    def boom(*a, **kw):
        raise RuntimeError("device store refused to start")

    monkeypatch.setattr(devstore.DeviceSegmentStore, "__init__", boom)
    cfg = Config()
    cfg.set("index.device.mesh", "off")
    with pytest.raises(RuntimeError, match="refused to start"):
        Switchboard(data_dir=None, config=cfg)
    # the explicit way to run without a device still works
    cfg.set("index.device.serving", "false")
    sb = Switchboard(data_dir=None, config=cfg)
    try:
        assert sb.index.devstore is None
    finally:
        sb.close()


def test_startup_p2p_honours_device_keys_from_yacy_conf(tmp_path):
    """The normal entry point (p2p=True) must build its Switchboard
    from the loaded yacy.conf, not from a default Config."""
    from yacy_search_server_tpu import yacy as launcher
    d = str(tmp_path / "DATA")
    os.makedirs(os.path.join(d, "SETTINGS"))
    with open(os.path.join(d, "SETTINGS", "yacy.conf"), "w") as f:
        f.write("index.device.budgetBytes=123456789\n"
                "index.device.mesh=off\n")
    node, http, lock = launcher.startup(d, port=0, p2p=True)
    try:
        assert node.sb.index.devstore.arena.budget_bytes == 123456789
    finally:
        node.close()
        http.close()
        launcher.release_lock(lock)


def test_failed_start_releases_the_lock(tmp_path, monkeypatch):
    from yacy_search_server_tpu import switchboard
    from yacy_search_server_tpu import yacy as launcher

    def boom(self):
        raise RuntimeError("no device")

    monkeypatch.setattr(switchboard.Switchboard, "_enable_device_serving",
                        boom)
    d = str(tmp_path / "DATA")
    with pytest.raises(RuntimeError, match="no device"):
        launcher.startup(d, port=0, p2p=False)
    assert not os.path.exists(os.path.join(d, "yacy.running"))


# -- roofline peaks ----------------------------------------------------------

class _Dev:
    device_kind = "Mystery Accelerator 9"


def test_device_peak_unknown_kind_raises(monkeypatch):
    from yacy_search_server_tpu.ops import roofline as RF
    monkeypatch.delenv("YACY_ROOFLINE_PEAK_FLOPS", raising=False)
    monkeypatch.delenv("YACY_ROOFLINE_PEAK_GBPS", raising=False)
    with pytest.raises(KeyError, match="mystery accelerator 9"):
        RF.device_peak(_Dev())
    # half a declaration is not a declaration
    monkeypatch.setenv("YACY_ROOFLINE_PEAK_FLOPS", "1e12")
    with pytest.raises(KeyError):
        RF.device_peak(_Dev())


def test_device_peak_unknown_kind_with_declared_peaks(monkeypatch):
    from yacy_search_server_tpu.ops import roofline as RF
    monkeypatch.setenv("YACY_ROOFLINE_PEAK_FLOPS", "1e12")
    monkeypatch.setenv("YACY_ROOFLINE_PEAK_GBPS", "100")
    peak = RF.device_peak(_Dev())
    assert (peak.flops_per_s, peak.bytes_per_s) == (1e12, 100e9)
    assert "declared" in peak.name


# -- the fusion collective ---------------------------------------------------

def test_fusion_collective_is_one_implementation_without_a_fallback():
    """The Pallas ring that `fused_gather_topk` chose on TPU meshes (and
    silently replaced by the lax path when it raised) is gone: one
    collective, no gate, no try/except around it."""
    from yacy_search_server_tpu.ops import roofline
    from yacy_search_server_tpu.parallel import mesh
    assert not hasattr(mesh, "fused_gather_topk")
    assert not hasattr(mesh, "_all_gather_topk_pallas")
    assert "_all_gather_topk_pallas" not in roofline.KERNELS
    tree = ast.parse(inspect.getsource(mesh.all_gather_topk))
    assert not [n for n in ast.walk(tree) if isinstance(n, ast.Try)]


# -- prewarm failures are counted --------------------------------------------

def test_prewarm_failure_is_counted(monkeypatch):
    import numpy as np

    from yacy_search_server_tpu.index import devstore, postings as P
    from yacy_search_server_tpu.index.postings import PostingsList
    from yacy_search_server_tpu.index.rwi import RWIIndex
    from yacy_search_server_tpu.utils.hashes import word2hash

    rwi = RWIIndex()
    rng = np.random.default_rng(1)
    rwi.ingest_run({word2hash("warmterm"): PostingsList(
        np.arange(512, dtype=np.int32),
        rng.integers(0, 1000, (512, P.NF)).astype(np.int32))})
    ds = devstore.DeviceSegmentStore(rwi)
    try:
        ds.enable_batching(max_batch=4, dispatchers=1, prewarm=False)

        def refused(*a, **kw):
            raise RuntimeError("compiler refused the shape")

        monkeypatch.setattr(devstore, "_rank_pruned_batch1_packed_kernel",
                            refused)
        ds.prewarm_kernels(kks=(16,))
        c = ds.counters()
        assert c["prewarm_failures"] == 1
        assert c["prewarm_shapes"] > 0      # later shapes still warmed
    finally:
        ds.close()


# -- the first concurrent requests after a start ------------------------------

_RACE = """
import sys, threading
sys.setswitchinterval(1e-6)
from yacy_search_server_tpu.server import servlets
got, go = [], threading.Barrier(16)
def ask():
    go.wait()
    got.append(servlets.lookup("yacysearch"))
ts = [threading.Thread(target=ask) for _ in range(16)]
[t.start() for t in ts]
[t.join(60) for t in ts]
assert not any(t.is_alive() for t in ts)
print(sum(g is not None for g in got))
"""


def test_servlet_registry_loads_once_under_concurrent_first_requests():
    """16 threads race the lazy registry load in a fresh interpreter:
    none may see an empty registry (it answered 200 with the raw
    template file)."""
    r = _run(["-c", _RACE], {"PYTHONPATH": REPO})
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip() == "16"


# -- the arena budget counts what the device reports -------------------------

def test_arena_refuses_a_growth_the_device_has_no_room_for():
    """would_fit charges the budget in measured device bytes per row and
    refuses a copy-on-write append whose old + new copies would not fit
    what memory_stats() reports — instead of overrunning into an OOM."""
    from yacy_search_server_tpu.index.devstore import (TILE, DeviceArena,
                                                       measure_row_bytes)

    arena = DeviceArena(budget_bytes=1 << 40)
    assert measure_row_bytes(arena.device) == DeviceArena.row_bytes()
    assert arena.would_fit(10 * TILE)        # the CPU reports no limit

    class Chip:                              # a device that does
        def __init__(self, in_use, limit):
            self.stats = {"bytes_in_use": in_use, "bytes_limit": limit}

        def memory_stats(self):
            return self.stats

    arena.device_row_bytes = 56.0            # the v5e's measured figure
    rows = 10 * TILE                         # grows 4*TILE -> 16*TILE
    new = 16 * TILE * 56 + arena._pw_cap * 4
    cur = int(arena._cap * 56.0) + arena._pw_cap * 4
    arena.device = Chip(in_use=cur, limit=2 * new)
    assert arena.would_fit(rows)
    arena.device = Chip(in_use=cur, limit=2 * new - 1)
    assert not arena.would_fit(rows)
    # the budget itself is charged at 56 B/row, not the logical 42
    arena.device = Chip(in_use=0, limit=1 << 40)
    arena.budget_bytes = 16 * TILE * 50
    assert not arena.would_fit(rows)
