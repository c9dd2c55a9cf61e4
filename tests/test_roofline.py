"""Silicon accounting tests (ISSUE 1 tentpole).

The cost models in ops/roofline.py claim closed-form FLOPs / bytes for
every serving kernel; these tests pin the flop counts against XLA's own
compiled cost analysis (within 10% on 3 representative shapes per
kernel; the compulsory bytes come from array shapes and have no
compiler to agree with), exercise the roofline math, and bound the
profiler's hot-path overhead (< 1% on a 1k-query microbench).

Loop-carried kernels (lax.scan / fori_loop / lax.map bodies) are
cross-checked at their UNIT-TRIP shape: HloCostAnalysis counts a loop
body once regardless of trip count, so the comparable analytical number
is the one-step cost (the model multiplies by the trip count for real
executions — that part is plain arithmetic, not an estimate).

Three kernels (the two bit-packed scorers, the BlockRank iteration) are
held to the module AS LOWERED (`lowered=True`): their optimised count
charges gathers/scatters by how the host's CPU pipeline expands them,
and read differently on two machines running the same jax.
"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from yacy_search_server_tpu.index import postings as P
from yacy_search_server_tpu.ops import dense as D
from yacy_search_server_tpu.ops import ranking as R
from yacy_search_server_tpu.ops import roofline as RF
from yacy_search_server_tpu.ops import streaming as S
from yacy_search_server_tpu.utils.profiler import RooflineProfiler

TOL = 0.10    # the 10% cross-check bar


def _xla(jitfn, *args, **kw):
    flops = RF.xla_cost(jitfn, *args, **kw)
    if np.isnan(flops):
        pytest.skip("backend does not expose cost_analysis")
    return flops


def _close(model: float, xla: float, what: str):
    assert xla > 0, f"{what}: xla reported {xla}"
    rel = abs(model - xla) / xla
    assert rel <= TOL, (f"{what}: model {model:.4g} vs xla {xla:.4g} "
                       f"({100 * rel:.1f}% off)")


def _consts(profile=None, lang="en"):
    prof = profile or R.RankingProfile()
    bits, shifts = prof.flag_coeffs()
    return (jnp.asarray(prof.norm_coeffs()), jnp.asarray(bits),
            jnp.asarray(shifts), jnp.int32(prof.domlength),
            jnp.int32(prof.tf), jnp.int32(prof.language),
            jnp.int32(prof.authority), jnp.int32(P.pack_language(lang)))


def _block(n):
    f16 = jnp.zeros((n, P.NF), jnp.int16)
    fl = jnp.zeros(n, jnp.int32)
    dd = jnp.arange(n, dtype=jnp.int32)
    v = jnp.ones(n, bool)
    hh = jnp.zeros(n, jnp.int32)
    return f16, fl, dd, v, hh


# -- registry shape ----------------------------------------------------------

def test_registry_covers_the_named_kernels():
    """Every kernel ISSUE 1 names carries a cost model."""
    for name in ("cardinal_scores16", "score_topk16", "scan_score_topk",
                 "stream_score_topk", "hybrid_rerank_topk_batch",
                 "_rank_spans_kernel", "_rank_pruned_batch1_kernel",
                 "_rank_join_batch_kernel", "_rank_join_bm_batch_kernel"):
        assert name in RF.KERNELS, name
    with pytest.raises(KeyError):
        RF.cost("no_such_kernel", n=1)


# -- cost model vs XLA (3 shapes per kernel) ---------------------------------

@pytest.mark.parametrize("ndev,k,rows", ((4, 16, 256), (8, 16, 1024),
                                         (8, 128, 256), (4, 64, 4096)))
def test_xla_all_gather_topk(ndev, k, rows):
    """The fused fusion collective's cost model vs XLA (ISSUE 12
    acceptance: flops XLA-cross-checked, gathered bytes scale with k
    not corpus rows) — the whole shard_map program: local tie-exact top-k
    + k-row gather + tie-pinned merge."""

    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as PS

    from yacy_search_server_tpu.parallel.mesh import (all_gather_topk,
                                                      tie_topk)
    devs = jax.devices("cpu")
    if len(devs) < ndev:
        pytest.skip(f"needs {ndev} virtual CPU devices")
    mesh = Mesh(np.asarray(devs[:ndev]), ("doc",))

    def body(s, d):
        ls, ld = tie_topk(s, d, k)
        return all_gather_topk(ls, ld, "doc", k)
    fn = jax.jit(jax.shard_map(body, mesh=mesh,
                               in_specs=(PS("doc"), PS("doc")),
                               out_specs=(PS(), PS()), check_vma=False))
    n = ndev * rows
    sa = jax.device_put(jnp.arange(n, dtype=jnp.int32),
                        NamedSharding(mesh, PS("doc")))
    da = jax.device_put(jnp.arange(n, dtype=jnp.int32),
                        NamedSharding(mesh, PS("doc")))
    flops = _xla(fn, sa, da)
    c = RF.cost("all_gather_topk", k=k, ndev=ndev, rows=rows)
    _close(c.flops, flops, f"all_gather_topk[{ndev},{k},{rows}] flops")
    # the k-scaling contract: quadrupling corpus rows grows the model's
    # gathered wire payload not at all (compulsory bytes: 8·G + local)
    big = RF.cost("all_gather_topk", k=k, ndev=ndev, rows=rows * 4)
    gathered = lambda c_, r: c_.bytes - 8.0 * r   # noqa: E731
    assert gathered(c, rows) == gathered(big, rows * 4)


@pytest.mark.parametrize("n", (4096, 32768, 131072))
def test_xla_cardinal_scores16(n):
    f16, fl, dd, v, hh = _block(n)
    cj = jax.jit(lambda *a: R.cardinal_scores16(*a, with_authority=False))
    flops = _xla(cj, f16, fl, v, hh, None, *_consts())
    c = RF.cost("cardinal_scores16", n=n)
    _close(c.flops, flops, f"cardinal_scores16[{n}] flops")


@pytest.mark.parametrize("n,k", ((4096, 16), (32768, 128), (131072, 16)))
def test_xla_score_topk16(n, k):
    f16, fl, dd, v, hh = _block(n)
    flops = _xla(R.score_topk16, f16, fl, dd, v, hh, *_consts(),
                 k=k, with_authority=False)
    c = RF.cost("score_topk16", n=n, k=k)
    _close(c.flops, flops, f"score_topk16[{n},{k}] flops")


@pytest.mark.parametrize("n,k", ((8192, 16), (32768, 16), (65536, 128)))
def test_xla_score_topk_int32(n, k):
    f = jnp.zeros((n, P.NF), jnp.int32)
    dd = jnp.arange(n, dtype=jnp.int32)
    v = jnp.ones(n, bool)
    hh = jnp.zeros(n, jnp.int32)
    flops = _xla(R.score_topk, f, dd, v, hh, *_consts(), k=k)
    c = RF.cost("score_topk", n=n, k=k)
    _close(c.flops, flops, f"score_topk[{n},{k}] flops")


@pytest.mark.parametrize("tile", (16384, 32768, 65536))
def test_xla_scan_score_topk_unit_step(tile):
    # lower a >=2-step trace (a 1-step scan fuses differently); compare
    # the model's one-step cost against the counted-once loop body
    n = 2 * tile
    f16, fl, dd, v, hh = _block(n)
    stats = {"col_min": jnp.zeros(P.NF, jnp.int32),
             "col_max": jnp.full(P.NF, 1000, jnp.int32),
             "tf_min": jnp.float32(0), "tf_max": jnp.float32(1),
             "host_counts": jnp.zeros(1, jnp.int32)}
    flops = _xla(S.scan_score_topk, f16, fl, dd, v, hh, stats,
                 *_consts(), k=16, tile=tile)
    c = RF.cost("scan_score_topk", n=tile, k=16, tile=tile)
    _close(c.flops, flops, f"scan_score_topk[{tile}] flops")


@pytest.mark.parametrize("n,t", ((32768, 3), (131072, 5), (32768, 8)))
def test_xla_bm25_topk(n, t):
    tf = jnp.ones((n, t), jnp.float32)
    dl = jnp.ones(n, jnp.int32)
    df = jnp.ones(t, jnp.int32)
    v = jnp.ones(n, bool)
    dd = jnp.arange(n, dtype=jnp.int32)
    flops = _xla(R.bm25_topk, tf, dl, df, jnp.int32(n), v, dd, k=16)
    c = RF.cost("bm25_topk", n=n, t=t, k=16)
    _close(c.flops, flops, f"bm25_topk[{n},{t}] flops")


@pytest.mark.parametrize("n", (32768, 65536, 131072))
def test_xla_hybrid_rerank_solo(n):
    dv = jnp.zeros((n, 256), jnp.float32)
    q = jnp.zeros(256, jnp.float32)
    flops = _xla(D.hybrid_rerank_topk, q, dv,
                 jnp.zeros(n, jnp.float32), jnp.ones(n, bool),
                 jnp.float32(0.5), k=128)
    c = RF.cost("hybrid_rerank_topk", n=n, k=128)
    _close(c.flops, flops, f"hybrid_rerank_topk[{n}] flops")


@pytest.mark.parametrize("n,b", ((32768, 16), (65536, 16), (65536, 8)))
def test_xla_hybrid_rerank_batch(n, b):
    q = jnp.zeros((b, 256), jnp.float32)
    dv = jnp.zeros((n, 256), jnp.float32)
    flops = _xla(D.hybrid_rerank_topk_batch, q, dv,
                 jnp.zeros((b, n), jnp.float32),
                 jnp.ones((b, n), bool), jnp.float32(0.5), k=128)
    c = RF.cost("hybrid_rerank_topk_batch", n=n, b=b, k=128)
    _close(c.flops, flops, f"hybrid_batch[{n},{b}] flops")


@pytest.mark.parametrize("n", (32768, 65536, 131072))
def test_xla_dense_boost(n):
    dv = jnp.zeros((n, 256), jnp.float32)
    q = jnp.zeros(256, jnp.float32)
    flops = _xla(D.dense_boost_topk, q, dv, jnp.zeros(n, jnp.int32),
                 jnp.ones(n, bool), jnp.float32(0.5), k=128)
    c = RF.cost("dense_boost_topk", n=n, k=128)
    _close(c.flops, flops, f"dense_boost[{n}] flops")


@pytest.mark.parametrize("nb,bs,cap", (
    (16, 4, 1 << 12), (128, 16, 1 << 14), (1024, 8, 1 << 14)))
def test_xla_rerank_fwd_batch_packed(nb, bs, cap):
    """The serving rerank family (ISSUE 6): bs fused descriptors
    gathering from a [cap, dim] f16 device-resident forward index."""
    fwd = jnp.zeros((cap, 256), jnp.float16)
    qi = jnp.zeros((bs, 2 + 2 * nb + 256), jnp.int32)
    flops = _xla(D._rerank_fwd_batch_packed_kernel, fwd, qi,
                 nb=nb, bs=bs)
    c = RF.cost("_rerank_fwd_batch_packed_kernel", bs=bs, nb=nb)
    _close(c.flops, flops, f"rerank_fwd[{nb},{bs},{cap}] flops")


@pytest.mark.parametrize("bs,C", ((4, 256), (16, 1024), (16, 4096)))
def test_xla_ann_assign(bs, C):
    """Dense-first centroid assignment (ISSUE 11): the (B,dim)×(dim,C)
    bf16 wave matmul."""
    from yacy_search_server_tpu.ops import ann as AN
    cent = jnp.zeros((C, 256), jnp.float16)
    qv = jnp.zeros((bs, 256), jnp.float32)
    flops = _xla(AN._ann_assign_batch_kernel, cent, qv, np_=8,
                 c_real=C)
    c = RF.cost("_ann_assign_batch_kernel", bs=bs, dim=256, C=C, np_=8)
    _close(c.flops, flops, f"ann_assign[{bs},{C}] flops")


@pytest.mark.parametrize("bs,nb,cap,k", ((4, 1024, 65536, 64),
                                         (16, 4096, 65536, 64),
                                         (8, 16384, 1 << 20, 256)))
def test_xla_ann_fuse(bs, nb, cap, k):
    """Dense-first probe/fuse (ISSUE 11): bs packed descriptors
    gathering int8 lanes from a [cap, dim] hot slab, dequant fused into
    the scoring matmul, two-key tie sort."""
    from yacy_search_server_tpu.ops import ann as AN
    slab = jnp.zeros((cap, 256), jnp.int8)
    scales = jnp.zeros(cap, jnp.float16)
    sdocids = jnp.zeros(cap, jnp.int32)
    qi = jnp.zeros((bs, 2 + 3 * nb + 256), jnp.int32)
    flops = _xla(AN._ann_fuse_batch_packed_kernel, slab, scales,
                 sdocids, qi, nb=nb, bs=bs, k=k)
    c = RF.cost("_ann_fuse_batch_packed_kernel", bs=bs, nb=nb, dim=256,
                k=k)
    _close(c.flops, flops, f"ann_fuse[{bs},{nb},{cap},{k}] flops")


@pytest.mark.parametrize("bs,rows", ((2, 256), (8, 1024), (16, 4096)))
def test_xla_pack_block_batch(bs, rows):
    """Device-side index build (ISSUE 13b): the write path's vmapped
    bit-pack — bs lanes laying rows-row blocks down as scatter-adds
    over the int32 word stream."""
    from yacy_search_server_tpu.ingest import devbuild as IB
    rng = np.random.default_rng(bs * 100 + rows)
    f16 = rng.integers(-100, 100, (bs, rows, P.NF)).astype(np.int16)
    fl = rng.integers(0, 1 << 20, (bs, rows)).astype(np.int32)
    dd = rng.integers(0, 1 << 20, (bs, rows)).astype(np.int32)
    nv = np.full(bs, rows, np.int32)
    flops = _xla(IB._pack_block_batch_kernel, f16, fl, dd, nv,
                 rows=rows)
    c = RF.cost("_pack_block_batch_kernel", bs=bs, rows=rows)
    _close(c.flops, flops, f"pack_block_batch[{bs},{rows}] flops")


@pytest.mark.parametrize("n,e", ((1024, 8192), (1024, 16384), (2048, 8192)))
def test_xla_power_iterate_unit_step(n, e):
    from yacy_search_server_tpu.ops import blockrank as B
    flops = _xla(B._power_iterate_sparse, jnp.zeros(e, jnp.int32),
                 jnp.zeros(e, jnp.int32), jnp.ones(e, jnp.float32),
                 jnp.zeros(n, bool), jnp.float32(0.85), n=n,
                 lowered=True)
    c = RF.cost("_power_iterate_sparse", n=n, edges=e, iters=1)
    _close(c.flops, flops, f"power[{n},{e}] flops")


# devstore kernels share one arena fixture (compiles are the slow part)
@pytest.fixture(scope="module")
def arena():
    from yacy_search_server_tpu.index.devstore import TILE
    cap = 4 * TILE
    return {
        "TILE": TILE, "cap": cap,
        "f16": jnp.zeros((cap, P.NF), jnp.int16),
        "fl": jnp.zeros(cap, jnp.int32),
        "dd": jnp.zeros(cap, jnp.int32),
        "dead": jnp.zeros(1 << 16, bool),
        "pmax": jnp.zeros(1 << 12, jnp.int32),
        "jd": jnp.full(1 << 17, 2 ** 31 - 1, jnp.int32),
        "jp": jnp.zeros(1 << 17, jnp.int32),
        "bmtab": jnp.zeros((2, 1 << 15, 2), jnp.int32),
    }


@pytest.mark.parametrize("bs,maxt", ((8, 64), (16, 64), (16, 128)))
def test_xla_rank_pruned_batch1(arena, bs, maxt):
    from yacy_search_server_tpu.index import devstore as DS
    z = np.zeros(bs, np.int32)
    zc = np.zeros((bs, P.NF), np.int32)
    zf = np.zeros(bs, np.float32)
    qi, qf, nbs = DS._pack_batch1(z, z, z, z, zc, zc, zf, zf,
                                  np.int32(0), np.int32(0))
    flops = _xla(DS._rank_pruned_batch1_kernel, arena["f16"],
                 arena["fl"], arena["dd"], arena["dead"],
                 arena["pmax"], qi, qf, *_consts(), k=16, maxt=maxt,
                 bs=nbs)
    c = RF.cost("_rank_pruned_batch1_kernel", bs=bs, tile=arena["TILE"],
                maxt=maxt, k=16)
    _close(c.flops, flops, f"pruned_batch1[{bs},{maxt}] flops")


@pytest.mark.parametrize("bs,pw_cap", ((4, 1 << 18), (16, 1 << 18),
                                       (16, 1 << 20)))
def test_xla_rank_pruned_batch1_bp(arena, bs, pw_cap):
    """The bit-packed fused-decode pruned kernel, against the module as
    lowered: optimised, each decode gather is charged per word of the
    packed arena (`pw_cap`), by a factor that moves with the XLA build;
    the model is per scored row at either capacity."""
    from yacy_search_server_tpu.index import devstore as DS
    from yacy_search_server_tpu.ops import packed as PK
    z = np.zeros(bs, np.int32)
    zc = np.zeros((bs, P.NF), np.int32)
    zf = np.zeros(bs, np.float32)
    zm = np.zeros((bs, PK.META_LEN), np.int32)
    qiq, nbs = DS._pack_batch1_bp(z, z, z, z, zm, zc, zc, zf, zf,
                                  np.int32(0), np.int32(0))
    flops = _xla(DS._rank_pruned_batch1_bp_kernel,
                 jnp.zeros(pw_cap, jnp.int32), arena["dead"],
                 arena["pmax"], qiq, *_consts(), k=16, maxt=64,
                 bs=nbs, lowered=True)
    c = RF.cost("_rank_pruned_batch1_bp_kernel", bs=bs,
                tile=arena["TILE"], maxt=64, k=16)
    _close(c.flops, flops, f"pruned_bp[{bs},{pw_cap}] flops")


@pytest.mark.parametrize("bs,pw_cap", ((1, 1 << 18), (4, 1 << 20)))
def test_xla_rank_scan_bp_unit_trip(arena, bs, pw_cap):
    """The bit-packed exact scan at its unit-trip shape (count = one
    TILE per slot; fori bodies count once in the XLA model)."""
    from yacy_search_server_tpu.index import devstore as DS
    from yacy_search_server_tpu.ops import packed as PK
    qi = np.zeros((bs, 6 + PK.META_LEN), np.int32)
    qi[:, 1] = arena["TILE"]
    flops = _xla(DS._rank_scan_batch_bp_kernel,
                 jnp.zeros(pw_cap, jnp.int32), arena["dead"], qi,
                 *_consts(), k=16, bs=bs, lowered=True)
    c = RF.cost("_rank_scan_batch_bp_kernel", rows=bs * arena["TILE"],
                k=16, bs=bs)
    _close(c.flops, flops, f"scan_bp[{bs},{pw_cap}] flops")


def test_xla_rank_pruned_unit_trip(arena):
    """lax.map + fori bodies count once: the comparable model shape is
    one slot × one tile (the unit trip)."""
    from yacy_search_server_tpu.index import devstore as DS
    z = np.zeros(16, np.int32)
    zc = np.zeros((16, P.NF), np.int32)
    zf = np.zeros(16, np.float32)
    flops = _xla(DS._rank_pruned_batch_kernel, arena["f16"],
                 arena["fl"], arena["dd"], arena["dead"],
                 arena["pmax"], z, z, z, z, zc, zc, zf, zf,
                 np.int32(0), np.int32(0), *_consts(), k=16, b=8)
    c = RF.cost("_rank_pruned_kernel", b=1, bs=1, tile=arena["TILE"],
                k=16)
    _close(c.flops, flops, "pruned unit-trip flops")


@pytest.mark.parametrize("r,m", ((65536, 65536), (131072, 65536),
                                 (65536, 131072)))
def test_xla_rank_join(arena, r, m):
    from yacy_search_server_tpu.index import devstore as DS
    qargs = np.zeros((1, 9), np.int32)
    flops = _xla(DS._rank_join_batch_kernel, arena["f16"],
                 arena["fl"], arena["dd"], arena["dead"],
                 arena["jd"], arena["jp"], qargs, *_consts(),
                 k=16, n_inc=1, n_exc=0, r=r, inc_ms=(m,), exc_ms=())
    c = RF.cost("_rank_join_batch_kernel", r=r, m=m, n_inc=1, n_exc=0,
                bs=1, k=16)
    _close(c.flops, flops, f"join[{r},{m}] flops")


@pytest.mark.parametrize("r,bs", ((65536, 1), (131072, 1), (65536, 4)))
def test_xla_rank_join_bm(arena, r, bs):
    from yacy_search_server_tpu.index import devstore as DS
    qargs = np.zeros((bs, 9), np.int32)
    flops = _xla(DS._rank_join_bm_batch_kernel, arena["f16"],
                 arena["fl"], arena["dd"], arena["dead"],
                 arena["jd"], arena["jp"], arena["bmtab"], qargs,
                 *_consts(), k=16, n_inc=1, n_exc=0, r=r,
                 inc_ms=(0,), exc_ms=(), inc_bm=(True,), exc_bm=())
    c = RF.cost("_rank_join_bm_batch_kernel", r=r, n_inc=1, n_exc=0,
                bs=bs, k=16)
    _close(c.flops, flops, f"join_bm[{r},{bs}] flops")


@pytest.mark.parametrize("k", (16, 128))
def test_xla_rank_spans(arena, k):
    from yacy_search_server_tpu.index import devstore as DS
    ns = DS.DeviceSegmentStore.MAX_SPANS
    d_args = (jnp.zeros((1, P.NF), jnp.int16), jnp.zeros(1, jnp.int32),
              jnp.full(1, -1, jnp.int32))
    zero_ext = (np.zeros(P.NF, np.int32), np.zeros(P.NF, np.int32),
                np.float32(0), np.float32(0))
    flops = _xla(
        DS._rank_spans_kernel, arena["f16"], arena["fl"], arena["dd"],
        arena["dead"], np.zeros(ns, np.int32), np.zeros(ns, np.int32),
        *d_args, jnp.zeros(1, jnp.uint32), np.int32(DS.NO_LANG),
        np.int32(DS.NO_FLAG), np.int32(DS.DAYS_NONE_LO),
        np.int32(DS.DAYS_NONE_HI), *zero_ext, *_consts(), k=k,
        n_spans=ns, with_delta=False)
    # unit trip: each span slot's stats + score fori bodies count once
    c = RF.cost("_rank_spans_kernel", rows=ns * arena["TILE"],
                n_spans=ns, k=k)
    _close(c.flops, flops, f"spans[{k}] flops")


# -- roofline math -----------------------------------------------------------

def test_bound_verdict_and_util():
    peak = RF.DevicePeak("test", 100e12, 1e12)   # ridge = 100 flops/byte
    mem = RF.roofline_point("m", RF.Cost(10e9, 1e9), 0.01, peak)
    assert mem.bound == "memory"
    # 1e9 bytes in 10 ms = 100 GB/s of a 1000 GB/s peak -> 10%
    assert mem.util_pct == pytest.approx(10.0, rel=1e-6)
    comp = RF.roofline_point("c", RF.Cost(200e9, 1e9), 0.01, peak)
    assert comp.bound == "compute"
    # 200e9 flops in 10 ms = 20 TFLOP/s of 100 TFLOP/s -> 20%
    assert comp.util_pct == pytest.approx(20.0, rel=1e-6)


def test_device_peak_env_override(monkeypatch):
    monkeypatch.setenv("YACY_ROOFLINE_PEAK_FLOPS", "1e12")
    monkeypatch.setenv("YACY_ROOFLINE_PEAK_GBPS", "100")
    peak = RF.device_peak()
    assert peak.flops_per_s == 1e12
    assert peak.bytes_per_s == 100e9
    assert "overridden" in peak.name


def test_ascii_table_renders():
    peak = RF.PEAKS["cpu"]
    pts = [RF.roofline_point("score_topk16",
                             RF.cost("score_topk16", n=1 << 20),
                             0.005, peak)]
    table = RF.ascii_table(pts, peak)
    assert "score_topk16" in table and "util%" in table


# -- profiler ----------------------------------------------------------------

def test_profiler_records_and_query_util():
    # ridge = 100 flops/byte (TPU-like): the int scorer (~13 flops/byte)
    # and even the b=16 rerank matmul (~7 flops/byte over its f32 doc
    # matrix) classify memory-bound — the honest verdict the subsystem
    # exists to surface
    p = RooflineProfiler(peak=RF.DevicePeak("t", 1e13, 1e11))
    p.record("score_topk16", 0.001, queries=4, n=1 << 20, k=16)
    p.record("hybrid_rerank_topk_batch", 0.002, queries=16, n=65536, b=16)
    snap = {pt.kernel: pt for pt in p.snapshot()}
    assert set(snap) == {"score_topk16", "hybrid_rerank_topk_batch"}
    assert snap["score_topk16"].bound == "memory"
    qu = p.query_util()
    assert qu["util_pct_p50"] > 0
    assert qu["bound"] in ("memory", "compute")
    # unknown kernels/shapes must be a no-op, never an error
    p.record("no_such_kernel", 0.001, n=10)
    p.record("score_topk16", 0.001, bogus_shape_param=3)


def test_profiler_overhead_under_one_percent():
    """record() rides the serving hot path: the latency it adds to a
    1k-query microbench must stay < 1% of its baseline wall.

    The added latency is measured directly (amortized record() cost ×
    1k calls) rather than as an A/B wall-clock difference: on a shared
    1-core CI box the A/B form's scheduler noise (observed 0.5-8% on
    identical code) swamps the microsecond-scale quantity under test.
    The baseline is a 1k-query × 2 ms-host-work loop — 2 ms is BELOW
    the real path's measured per-query host time (3-7 ms in
    test_host_latency_budget), so the bound is conservative."""
    p = RooflineProfiler(peak=RF.DevicePeak("t", 1e12, 1e11))
    queries = 1000
    work_s = 0.002

    def baseline() -> float:
        t0 = time.perf_counter()
        for _ in range(queries):
            t = time.perf_counter()
            while time.perf_counter() - t < work_s:
                pass
        return time.perf_counter() - t0

    def record_cost(calls: int = 5000) -> float:
        t0 = time.perf_counter()
        for _ in range(calls):
            p.record("score_topk16", 0.001, queries=1, n=1 << 15, k=16)
        return (time.perf_counter() - t0) / calls

    p.record("score_topk16", 0.001, queries=1, n=1 << 15, k=16)  # warm
    base = baseline()
    added = min(record_cost() for _ in range(3)) * queries
    overhead = added / base
    assert overhead < 0.01, (
        f"profiler adds {added * 1e3:.2f} ms to a {base * 1e3:.0f} ms "
        f"1k-query microbench ({100 * overhead:.2f}%)")


def test_roofline_servlet_numbers_and_chart():
    """Performance_Roofline_p: numeric rows carry the per-query util
    percentiles and one row per profiled kernel; format=png renders a
    decodable roofline chart via the raster layer."""
    from yacy_search_server_tpu.server.objects import ServerObjects
    from yacy_search_server_tpu.server.servlets import lookup
    from yacy_search_server_tpu.utils.profiler import PROFILER

    fn = lookup("Performance_Roofline_p")
    assert fn is not None
    PROFILER.clear()
    PROFILER.record("score_topk16", 0.002, queries=3, n=1 << 18, k=16)
    PROFILER.record("_rank_spans_kernel", 0.004, queries=1,
                    rows=1 << 18, n_spans=8, k=16)
    try:
        prop = fn({}, ServerObjects(), None)
        assert prop.get_int("kernels") == 2
        names = {prop.get(f"kernels_{i}_name") for i in range(2)}
        assert names == {"score_topk16", "_rank_spans_kernel"}
        assert float(prop.get("kernels_0_util_pct")) > 0
        assert prop.get("kernels_0_bound") in ("memory", "compute")
        assert float(prop.get("util_pct_p50")) > 0
        assert float(prop.get("util_pct_p95")) >= \
            float(prop.get("util_pct_p50"))
        post = ServerObjects()
        post.put("format", "png")
        img = fn({}, post, None)
        assert img.raw_ctype == "image/png"
        assert img.raw_body[:8] == b"\x89PNG\r\n\x1a\n"
        assert len(img.raw_body) > 500
    finally:
        PROFILER.clear()


def test_profiler_record_is_microseconds():
    """The absolute cost behind the <1% claim: a memoized-shape record()
    stays in single-digit microseconds."""
    p = RooflineProfiler(peak=RF.DevicePeak("t", 1e12, 1e11))
    p.record("score_topk16", 0.001, queries=1, n=1 << 15, k=16)
    # best-of-3 with GC paused: the claim is record()'s own cost — a
    # major-GC pass over a session-grown heap landing inside one timed
    # window is suite noise, not profiler cost
    import gc
    n = 5000
    per_us = float("inf")
    gc.disable()
    try:
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(n):
                p.record("score_topk16", 0.001, queries=1, n=1 << 15,
                         k=16)
            per_us = min(per_us, (time.perf_counter() - t0) / n * 1e6)
    finally:
        gc.enable()
    assert per_us < 10.0, f"record() costs {per_us:.1f} us"
