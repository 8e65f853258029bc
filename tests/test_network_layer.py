"""Modeled network layer: fabric models, heatmaps, parity, O(structs) scale.

ISSUE 9's acceptance bar: ``repro.core.network`` maps each unique structure
in the trace's ``StructTable`` onto parameterized fabric models and emits
``layer="network"`` rows per region — golden halo-exchange heatmap fixtures
for the paper's three apps, numpy vs jax bit-identity on the modeled wire
times, the three-layer ``network_vs_traced`` join, and an O(unique structs)
assertion at the 8192-rank kripke scale point (no per-event arrays anywhere
in the reduction).
"""

import numpy as np

from repro.apps.amg import AMGConfig
from repro.apps.amg import profile as amg_profile
from repro.apps.kripke import KripkeConfig
from repro.apps.kripke import profile as kripke_profile
from repro.apps.laghos import LaghosConfig
from repro.apps.laghos import profile as laghos_profile
from repro.apps.stencil import Decomp3D
from repro.core.backend import NumpyBackend
from repro.core.hlo import scan_hlo_collectives
from repro.core.network import (
    DRAGONFLY,
    FABRICS,
    FAT_TREE,
    RING,
    NetworkModeledProfiler,
    ascii_heatmap,
    heatmap_csv,
    peer_heatmap,
    resolve_fabric,
    struct_costs,
    struct_fingerprints,
)
from repro.core.profiler import trace_observer
from repro.core.reports import network_vs_traced
from repro.core.thicket import Frame


def _trace(profile_fn, cfg, name="t"):
    holder = {}

    def keep(rec, *, name, replication, meta):
        holder["rec"] = rec
        return None

    with trace_observer(keep):
        prof = profile_fn(cfg, name=name)
    return prof, holder["rec"]


def _kripke_2x2x2():
    return _trace(
        kripke_profile,
        KripkeConfig(decomp=Decomp3D(2, 2, 2), nx=4, ny=4, nz=4, n_octants=1),
        name="kripke-8",
    )


# ---------------------------------------------------------------------------
# Fabric models: hops / link ids / link counts
# ---------------------------------------------------------------------------


def test_ring_hops_are_min_ring_distance():
    src = np.array([0, 0, 0, 7, 3])
    dst = np.array([1, 7, 4, 0, 3])
    assert RING.hops(src, dst, 8).tolist() == [1, 1, 4, 1, 0]
    assert RING.n_links(8) == 16
    # direction-resolved link ids: 2*src + (going the long way round)
    assert RING.link_ids(np.array([2]), np.array([3]), 8).tolist() == [4]
    assert RING.link_ids(np.array([2]), np.array([1]), 8).tolist() == [5]


def test_fat_tree_hops_by_leaf_membership():
    src = np.array([0, 0, 0, 17])
    dst = np.array([0, 15, 16, 40])
    assert FAT_TREE.hops(src, dst, 64).tolist() == [0, 2, 4, 4]
    # intra-leaf uses the source injection link, inter-leaf its uplink
    assert FAT_TREE.link_ids(src, dst, 64).tolist() == [0, 0, 64, 65]
    assert FAT_TREE.n_links(64) == 64 + 4


def test_dragonfly_hops_by_group_membership():
    src = np.array([0, 0, 0])
    dst = np.array([0, 15, 16])
    assert DRAGONFLY.hops(src, dst, 64).tolist() == [0, 1, 3]
    assert DRAGONFLY.n_links(64) == 64 + 4


def test_resolve_fabric_names():
    assert resolve_fabric(None) is RING
    assert resolve_fabric("fat-tree") is FAT_TREE
    assert resolve_fabric(DRAGONFLY) is DRAGONFLY
    assert set(FABRICS) == {"ring", "fat-tree", "dragonfly"}
    try:
        resolve_fabric("torus")
        raise AssertionError("unknown fabric must raise")
    except ValueError:
        pass


# ---------------------------------------------------------------------------
# Golden halo-exchange heatmaps (paper Fig 8 fixtures, small ranks)
# ---------------------------------------------------------------------------

#: kripke 2x2x2 sweep_comm: one directed plane send per +axis neighbor
#: (rank = 4x + 2y + z), so the matrix is the strictly-upper sweep DAG.
_KRIPKE_8 = [
    [0, 1, 1, 0, 1, 0, 0, 0],
    [0, 0, 0, 1, 0, 1, 0, 0],
    [0, 0, 0, 1, 0, 0, 1, 0],
    [0, 0, 0, 0, 0, 0, 0, 1],
    [0, 0, 0, 0, 0, 1, 1, 0],
    [0, 0, 0, 0, 0, 0, 0, 1],
    [0, 0, 0, 0, 0, 0, 0, 1],
    [0, 0, 0, 0, 0, 0, 0, 0],
]

#: amg 2x2x2 MatVecComm: symmetric +-axis halo, one send per neighbor.  The
#: 8x8x8 global grid has no distributed level (the coarse solve gathers at
#: once), so the region holds only PCG's one matvec halo per iteration, and
#: n_cycles is 1.
_AMG_8 = [
    [0, 1, 1, 0, 1, 0, 0, 0],
    [1, 0, 0, 1, 0, 1, 0, 0],
    [1, 0, 0, 1, 0, 0, 1, 0],
    [0, 1, 1, 0, 0, 0, 0, 1],
    [1, 0, 0, 0, 0, 1, 1, 0],
    [0, 1, 0, 0, 1, 0, 0, 1],
    [0, 0, 1, 0, 1, 0, 0, 1],
    [0, 0, 0, 1, 0, 1, 1, 0],
]

#: laghos 2x2x1 halo_exchange: 2D symmetric halo, eight sends per neighbor.
_LAGHOS_4 = [
    [0, 8, 8, 0],
    [8, 0, 0, 8],
    [8, 0, 0, 8],
    [0, 8, 8, 0],
]


def _reference_heatmap(rec, region=None):
    """Independent per-row expansion of the struct-interned trace."""
    buf = rec.buffer
    view = buf.structs.reduction_view()
    dip = view.dest_indptr()
    rip = view.rank_indptr()
    n = int(view.rank_lens.max()) if view.rank_lens.size else 0
    H = np.zeros((n, n), dtype=np.int64)
    rid = buf.region_names.index(region) if region is not None else None
    for i in range(buf.n_rows):
        if rid is not None and int(buf.region_ids[i]) != rid:
            continue
        s = int(buf.struct_ids[i])
        m = int(buf.multiplicity[i])
        rows = view.dest_rows[dip[s] : dip[s + 1]]
        peers = view.dest_peers[dip[s] : dip[s + 1]]
        for r, p in zip(rows, peers):
            H[int(r), int(p)] += m
        if view.dest_lens[s] == 0:
            members = view.participants[rip[s] : rip[s + 1]]
            if members.size >= 2:
                for a, b in zip(members, np.roll(members, -1)):
                    H[int(a), int(b)] += m
    return H


def test_golden_heatmaps_three_apps():
    cases = [
        (_kripke_2x2x2(), "sweep_comm", _KRIPKE_8),
        (
            _trace(amg_profile, AMGConfig(decomp=Decomp3D(2, 2, 2), nx=4, ny=4, nz=4)),
            "MatVecComm",
            _AMG_8,
        ),
        (
            _trace(
                laghos_profile,
                LaghosConfig(decomp=Decomp3D(2, 2, 1), nx=16, ny=16),
            ),
            "halo_exchange",
            _LAGHOS_4,
        ),
    ]
    for (prof, rec), region, golden in cases:
        H = peer_heatmap(rec, region=region)
        assert H.tolist() == golden, region
        ref = _reference_heatmap(rec, region=region)
        assert np.array_equal(H, ref), region


def test_heatmap_all_regions_matches_reference_and_binning():
    prof, rec = _kripke_2x2x2()
    H = peer_heatmap(rec)
    assert np.array_equal(H, _reference_heatmap(rec))
    # 8 ranks -> 4 bins of 2: totals preserved, shape reduced
    B = peer_heatmap(rec, bins=4)
    assert B.shape == (4, 4) and B.sum() == H.sum()
    assert B[0, 0] == H[:2, :2].sum()
    # unknown region: empty selection, not an exception
    assert peer_heatmap(rec, region="no-such-region").sum() == 0


def test_heatmap_renderers():
    prof, rec = _kripke_2x2x2()
    H = peer_heatmap(rec, region="sweep_comm")
    art = ascii_heatmap(H, title="kripke")
    assert art.splitlines()[0] == "## kripke"
    assert len(art.splitlines()) == 2 + H.shape[0]  # title + legend + rows
    csv = heatmap_csv(H)
    lines = csv.splitlines()
    assert lines[0].startswith("src\\dst,0,1")
    assert len(lines) == 1 + H.shape[0]
    assert lines[1].split(",")[1:] == [str(v) for v in H[0].tolist()]


def test_struct_fingerprints_surface_generators():
    prof, rec = _kripke_2x2x2()
    fps = struct_fingerprints(rec.buffer.structs)
    gens = {fp[0][0] for fp in fps.values() if isinstance(fp[0], tuple)}
    assert "kripke-plane" in gens


# ---------------------------------------------------------------------------
# Modeled region rows: content, fabric sensitivity, backend parity
# ---------------------------------------------------------------------------


def test_region_rows_kripke_ring_golden():
    prof, rec = _kripke_2x2x2()
    rows = NetworkModeledProfiler.region_rows(rec, fabric=RING, name="k8")
    by_region = {r["region"]: r for r in rows}
    r = by_region["sweep_comm"]
    assert r["layer"] == "network" and r["net_fabric"] == "ring"
    assert r["net_structs"] == 3 and r["net_msgs"] == 12
    assert r["net_hops_total"] == 28 and r["net_hops_max"] == 4
    assert r["net_links_used"] == 7 and r["net_link_msgs_max"] == 3
    assert r["net_congestion"] == 1.75
    assert r["net_wire_s"] == 9.21184e-06
    assert r["net_generators"] == "kripke-plane"


def test_region_rows_fabrics_differ():
    prof, rec = _kripke_2x2x2()
    wire = {}
    for fab in (RING, FAT_TREE, DRAGONFLY):
        rows = NetworkModeledProfiler.region_rows(rec, fabric=fab)
        wire[fab.name] = {r["region"]: r["net_wire_s"] for r in rows}
    # same trace, different modeled topology: hop terms must differ
    assert wire["ring"]["sweep_comm"] > wire["fat-tree"]["sweep_comm"]
    assert wire["fat-tree"]["sweep_comm"] > wire["dragonfly"]["sweep_comm"]


def test_region_rows_numpy_jax_bit_identical():
    for prof, rec in (
        _kripke_2x2x2(),
        _trace(amg_profile, AMGConfig(decomp=Decomp3D(2, 2, 2), nx=4, ny=4, nz=4)),
        _trace(laghos_profile, LaghosConfig(decomp=Decomp3D(2, 2, 1), nx=16, ny=16)),
    ):
        for fab in FABRICS.values():
            ref = NetworkModeledProfiler.region_rows(rec, fabric=fab, backend="numpy")
            jx = NetworkModeledProfiler.region_rows(rec, fabric=fab, backend="jax")
            assert ref == jx, fab.name


def test_frame_from_network_and_three_layer_join():
    prof, rec = _kripke_2x2x2()
    net = Frame.from_network([(prof.name, prof.n_ranks, rec, RING)])
    assert set(net.column("layer")) == {"network"}
    assert "sweep_comm" in net.column("region")

    hlo_text = """HloModule m
%add.r (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(f32[] %a, f32[] %b)
}

ENTRY %main (p0: f32[64,4]) -> f32[64,4] {
  %p0 = f32[64,4]{1,0} parameter(0)
  ROOT %ar = f32[64,4]{1,0} all-reduce(f32[64,4]{1,0} %p0), channel_id=1, \
replica_groups=[1,8]<=[8], to_apply=%add.r, \
metadata={op_name="jit(f)/commr::sweep_comm/psum"}
}
"""
    buf = scan_hlo_collectives(hlo_text, 8)
    md = network_vs_traced(
        [prof],
        [(prof.name, 8, rec, fab) for fab in (RING, FAT_TREE)],
        hlo_entries=[(prof.name, 8, buf)],
    )
    lines = md.splitlines()
    assert lines[0].startswith("| Profile | Region | Traced bytes |")
    row = next(ln for ln in lines if "| sweep_comm |" in ln)
    traced = sum(
        s.total_bytes_sent
        for s in prof.regions.values()
        if s.region == "sweep_comm"
    )
    assert f"| {traced} |" in row
    assert "| 12 |" not in lines[0]  # sanity: data rows only below header
    # both fabrics contribute: msgs doubled relative to a single entry
    assert "| 24 |" in row  # 12 msgs x 2 fabric row sets
    # hlo layer joined: wire bytes from the snippet appear in the row
    assert f"| {buf.summarize().total_wire_bytes} |" in row
    # empty inputs degrade to header only
    assert network_vs_traced([], []).count("\n") == 1


# ---------------------------------------------------------------------------
# O(unique structs) at the 8192-rank scale point
# ---------------------------------------------------------------------------


class _SpyBackend(NumpyBackend):
    """Records every matmul operand shape flowing through the reduction."""

    def __init__(self):
        super().__init__()
        self.shapes = []

    def matmul(self, a, b):
        self.shapes.append((tuple(np.shape(a)), tuple(np.shape(b))))
        return super().matmul(a, b)


def test_network_rows_scale_by_unique_structs_at_8192_ranks():
    cfg = KripkeConfig(
        decomp=Decomp3D(32, 32, 8),
        nx=16,
        ny=32,
        nz=32,
        n_octants=1,
        fuse_messages=True,
    )
    prof, rec = _trace(kripke_profile, cfg, name="kripke-8192")
    buf = rec.buffer
    S = buf.structs.n_structs
    total_sends = sum(s.total_sends for s in prof.regions.values())
    assert prof.n_ranks == 8192
    assert total_sends >= 50 * S, (total_sends, S)

    spy = _SpyBackend()
    rows = NetworkModeledProfiler.region_rows(rec, fabric=RING, backend=spy)
    assert rows and any(r["net_msgs"] for r in rows)
    G = len(buf.region_names)
    L = RING.n_links(8192)
    bound = max(G, S, L)
    assert spy.shapes, "reduction must route through the backend matmul"
    for a_shape, b_shape in spy.shapes:
        for dim in a_shape + b_shape:
            assert dim <= bound, (a_shape, b_shape)
            # per-event scaling would show up as a >=total_sends dim
            assert dim < total_sends, (a_shape, b_shape)
