"""Selection subsystem: candidate coverage, crossover behavior, measured
calibration beating priors, tuning-table persistence, topology link
metadata, error-budget codec gating, and the 8-device algo="auto"
equivalence check."""
import jax
import numpy as np
import pytest

from repro.core import autotune, compress, costmodel, mcoll
from repro.core.autotune import Selector, TuningTable
from repro.core.topology import Topology, derive_link
from repro.launch.mesh import make_mesh

from subproc import run_check

SIX = ("allgather", "scatter", "broadcast", "allreduce", "reduce_scatter",
       "alltoall")

# algorithms whose latency scales with round count (log-ish), vs the
# bandwidth-optimal ones that win at large sizes (the chunked pipelines
# belong to the bandwidth regime: chunking amortizes round latency)
LOW_ROUND = {"pip_mcoll", "recursive_doubling", "bruck", "binomial",
             "single_leader", "linear"}
BANDWIDTH = {"xla", "ring", "ring_pipeline", "pip_pipeline"}


# ---------------------------------------------------------------------------
# candidate registry: full coverage, no drift from mcoll
# ---------------------------------------------------------------------------


def test_candidates_cover_every_implemented_algorithm():
    """Regression for the old _CANDIDATES gaps (bruck missing, three
    collectives absent): candidates == the mcoll registry."""
    for coll in SIX:
        assert autotune.candidates(coll) == tuple(mcoll.algorithms(coll))


def test_cost_fns_cover_every_candidate():
    """Every registered algorithm has a cost-model branch."""
    topo = Topology(4, 4)
    for coll in SIX:
        fn = costmodel.COST_FNS[coll]
        for algo in autotune.candidates(coll, topo):
            c = fn(algo, topo, 1024, costmodel.tpu_v5e_pod())
            assert c.time > 0, (coll, algo)


def test_recursive_doubling_filtered_on_non_pow2():
    assert "recursive_doubling" not in autotune.candidates(
        "allgather", Topology(3, 2))
    assert "recursive_doubling" in autotune.candidates(
        "allgather", Topology(4, 2))


# ---------------------------------------------------------------------------
# crossover: small -> low-round, large -> bandwidth-optimal, no oscillation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("coll", SIX)
def test_crossover_monotone_small_latency_large_bandwidth(coll):
    topo = Topology(16, 16, node_link="tpu_v5e_ici", local_link="tpu_v5e_ici")
    sel = Selector()
    table = sel.crossover_table(coll, topo)
    sizes = sorted(table)
    assert table[sizes[0]].algo in LOW_ROUND, (coll, table[sizes[0]])
    assert table[sizes[-1]].algo in (BANDWIDTH if coll != "scatter"
                                     else LOW_ROUND | BANDWIDTH), coll
    # monotone: once a bandwidth-optimal algorithm wins, larger sizes never
    # fall back to a latency-bound one
    seen_bandwidth = False
    for s in sizes:
        if table[s].algo in BANDWIDTH:
            seen_bandwidth = True
        elif seen_bandwidth:
            pytest.fail(f"{coll}: crossover oscillated at {s}B "
                        f"-> {table[s].algo}")


def test_choose_small_prefers_multiobject_on_paper_cluster():
    topo = Topology(128, 18, node_link="pip", local_link="pip")
    sel = Selector()
    s = sel.choose("allgather", topo, 64)
    assert s.algo == "pip_mcoll" and s.source == "prior"
    assert s.chunks == 1, "latency regime must not chunk"


def test_choose_large_plans_chunked_pipeline():
    """The bandwidth regime resolves to a chunked pipelined plan: the
    chunk count is part of the selection, >1 only where the model says
    pipelining pays (the crossover vs. the unchunked variant)."""
    topo = Topology(16, 16, node_link="tpu_v5e_dcn", local_link="tpu_v5e_ici")
    sel = Selector()
    small = sel.choose("allreduce", topo, 256)
    assert small.chunks == 1, small
    large = sel.choose("allreduce", topo, 1 << 24)
    assert large.algo == "pip_pipeline" and large.chunks > 1, large
    net = costmodel.net_for(topo)
    unchunked = costmodel.allreduce_cost("pip_pipeline", topo, 1 << 24, net,
                                         chunks=1).time
    assert large.seconds < unchunked, "chunked plan must beat unchunked"


def test_measured_chunked_plan_decodes():
    """A measured plan key ("algo#cN") resolves to (algo, chunks)."""
    topo = Topology(4, 2)
    sel = Selector()
    sel.table.record(topo, "allreduce", "float32", 1 << 20, "xla", 1e-3)
    sel.table.record(topo, "allreduce", "float32", 1 << 20,
                     autotune.encode_plan("pip_pipeline", 8), 1e-6)
    s = sel.choose("allreduce", topo, 1 << 20)
    assert (s.algo, s.chunks, s.source) == ("pip_pipeline", 8, "measured")


# ---------------------------------------------------------------------------
# error budget: codec plan gating (the accuracy contract)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("coll", SIX)
def test_zero_budget_provably_never_lossy(coll):
    """With error_budget=0.0 (the default) the selector can never emit a
    lossy plan: (a) candidate enumeration admits only "none", (b) a full
    size sweep on every topology class resolves codec="none", (c) even a
    poisoned tuning table with fast lossy measurements cannot leak one."""
    for algo in autotune.candidates(coll):
        assert autotune.codec_candidates(coll, algo, 0.0) == ("none",)
    for topo in (Topology(16, 16, node_link="tpu_v5e_dcn",
                          local_link="tpu_v5e_ici"),
                 Topology(128, 18, node_link="pip", local_link="pip"),
                 Topology(1, 8), Topology(4, 2)):
        sel = Selector()
        for i in range(4, 27):
            s = sel.choose(coll, topo, 1 << i)  # default budget: 0.0
            assert s.codec == "none", (coll, topo, 1 << i, s)
    # poisoned table: lossy plan measured fastest in the bucket
    topo = Topology(4, 2)
    sel = Selector()
    for algo in autotune.candidates(coll, topo):
        if mcoll.supports_codec(coll, algo):
            sel.table.record(topo, coll, "float32", 1 << 20,
                             autotune.encode_plan(algo, 1, "topk"), 1e-12)
    sel.table.record(topo, coll, "float32", 1 << 20, "xla", 1e-3)
    s = sel.choose(coll, topo, 1 << 20)
    assert s.codec == "none", s
    # ... while a permissive budget may use the measured lossy entry
    if any(mcoll.supports_codec(coll, a)
           for a in autotune.candidates(coll, topo)):
        s2 = sel.choose(coll, topo, 1 << 20, error_budget=1.0)
        assert s2.codec == "topk" and s2.source == "measured", s2


def test_budget_admits_codecs_and_compressed_wins_bandwidth_regime():
    """Under a budget, the large-message prior resolves to a codec plan
    that strictly beats the lossless plan; the admitted codec respects the
    bound ordering (tighter budget -> tighter codec)."""
    topo = Topology(16, 16, node_link="tpu_v5e_dcn", local_link="tpu_v5e_ici")
    sel = Selector()
    lossless = sel.choose("allreduce", topo, 1 << 24)
    b_int8 = compress.meta("int8_block").error_bound
    s = sel.choose("allreduce", topo, 1 << 24, error_budget=b_int8)
    assert s.codec == "int8_block", s
    assert s.seconds < lossless.seconds
    s2 = sel.choose("allreduce", topo, 1 << 24, error_budget=1.0)
    assert s2.codec != "none"
    assert s2.seconds <= s.seconds
    # small messages stay lossless even under an unlimited budget: the
    # codec flop cost cannot buy anything in the latency-bound regime
    small = sel.choose("allreduce", topo, 64, error_budget=1.0)
    assert small.codec == "none", small


def test_budget_is_part_of_the_memo_key():
    """The same (collective, size) resolved under different budgets must
    not share memoized Selections."""
    topo = Topology(16, 16, node_link="tpu_v5e_dcn", local_link="tpu_v5e_ici")
    sel = Selector()
    a = sel.choose("allreduce", topo, 1 << 24, error_budget=0.0)
    b = sel.choose("allreduce", topo, 1 << 24, error_budget=1.0)
    assert a.codec == "none" and b.codec != "none"
    assert sel.choose("allreduce", topo, 1 << 24).codec == "none"


def test_measured_codec_plan_decodes_and_respects_budget():
    """A measured "algo#cN@codec" plan resolves to its full triple under an
    admitting budget, and is filtered under a tighter one."""
    topo = Topology(4, 2)
    sel = Selector()
    sel.table.record(topo, "allreduce", "float32", 1 << 20, "xla", 1e-3)
    sel.table.record(
        topo, "allreduce", "float32", 1 << 20,
        autotune.encode_plan("pip_pipeline", 8, "int8_block"), 1e-6)
    s = sel.choose("allreduce", topo, 1 << 20,
                   error_budget=compress.meta("int8_block").error_bound)
    assert (s.algo, s.chunks, s.codec, s.source) == \
        ("pip_pipeline", 8, "int8_block", "measured")
    tight = sel.choose("allreduce", topo, 1 << 20, error_budget=1e-6)
    assert tight.codec == "none" and tight.algo == "xla"


def test_unknown_codec_in_table_skipped():
    """A table recorded by a build with extra codecs must not crash or be
    selected — unknown codec names are skipped."""
    topo = Topology(4, 2)
    sel = Selector()
    sel.table.record(topo, "allreduce", "float32", 256,
                     "pip_mcoll@future_codec", 1e-12)
    sel.table.record(topo, "allreduce", "float32", 256, "xla", 1e-3)
    s = sel.choose("allreduce", topo, 256, error_budget=1.0)
    assert s.algo == "xla" and s.source == "measured"


def test_integer_dtypes_force_lossless_resolution():
    """auto with a positive budget on integer/bool payloads must resolve
    lossless (the compressed execution rejects integer payloads, so the
    selector must never plan one) — including from a poisoned table."""
    topo = Topology(16, 16, node_link="tpu_v5e_dcn", local_link="tpu_v5e_ici")
    sel = Selector()
    for dt in ("int32", "int8", "uint8", "bool"):
        s = sel.choose("allreduce", topo, 1 << 24, dtype=dt,
                       error_budget=1.0)
        assert s.codec == "none", (dt, s)
    # float dtypes are unaffected
    assert sel.choose("allreduce", topo, 1 << 24, dtype="bfloat16",
                      error_budget=1.0).codec != "none"
    t2 = Topology(4, 2)
    sel2 = Selector()
    sel2.table.record(t2, "allreduce", "int32", 1 << 20,
                      autotune.encode_plan("pip_mcoll", 1, "topk"), 1e-12)
    sel2.table.record(t2, "allreduce", "int32", 1 << 20, "xla", 1e-3)
    s = sel2.choose("allreduce", t2, 1 << 20, dtype="int32",
                    error_budget=1.0)
    assert s.codec == "none" and s.algo == "xla"


def test_codec_candidates_only_for_capable_algorithms():
    assert autotune.codec_candidates("allreduce", "xla", 1.0) == ("none",)
    assert autotune.codec_candidates("broadcast", "xla", 1.0) == ("none",)
    bcast = autotune.codec_candidates("broadcast", "pip_mcoll", 1.0)
    assert bcast[0] == "none" and set(compress.lossy()) <= set(bcast)
    cands = autotune.codec_candidates("allreduce", "pip_mcoll", 1.0)
    assert cands[0] == "none" and set(compress.lossy()) <= set(cands)


def test_codec_candidates_integer_payloads():
    """Lossy codecs never appear for integer payloads; the lossless packer
    does — but only on non-reducing collectives."""
    bcast = autotune.codec_candidates("broadcast", "pip_mcoll", 1.0,
                                      dtype="int32")
    assert "zlib_sim" in bcast
    assert not set(compress.lossy()) & set(bcast)
    ar = autotune.codec_candidates("allreduce", "pip_mcoll", 1.0,
                                   dtype="int32")
    assert ar == ("none",)
    f32 = autotune.codec_candidates("broadcast", "pip_mcoll", 0.0)
    assert "zlib_sim" not in f32  # integer-only packer stays off floats


def test_plan_cost_prices_codec_wire_and_flops():
    """plan_cost scales the wire beta by the codec ratio and adds the flop
    term: compressed is cheaper at bandwidth-bound sizes, costlier at
    latency-bound ones."""
    topo = Topology(16, 16, node_link="tpu_v5e_dcn", local_link="tpu_v5e_ici")
    net = costmodel.net_for(topo)
    big_l = costmodel.plan_cost("allreduce", "pip_mcoll", topo, 1 << 24, net)
    big_c = costmodel.plan_cost("allreduce", "pip_mcoll", topo, 1 << 24,
                                net, codec="int8_block")
    assert big_c.time < big_l.time
    assert big_c.inter_bytes_per_nic < big_l.inter_bytes_per_nic
    tiny_l = costmodel.plan_cost("allreduce", "pip_mcoll", topo, 16, net)
    tiny_c = costmodel.plan_cost("allreduce", "pip_mcoll", topo, 16, net,
                                 codec="int8_block")
    assert tiny_c.time >= tiny_l.time * 0.999  # flops >= wire savings
    xo = costmodel.compressed_crossover_bytes("allreduce", "pip_pipeline",
                                              topo, net, "int8_block")
    assert xo is not None and xo >= 64


# ---------------------------------------------------------------------------
# measured calibration beats the prior; stats track sources
# ---------------------------------------------------------------------------


def test_measured_entry_overrides_prior_and_counts():
    topo = Topology(4, 2)
    sel = Selector()
    prior = sel.choose("allgather", topo, 256)
    assert prior.source == "prior"
    # fake calibration: "ring" measured fastest in the 256B bucket
    for algo in autotune.candidates("allgather", topo):
        sel.table.record(topo, "allgather", "float32", 256, algo,
                         1e-6 if algo == "ring" else 1e-3)
    s = sel.choose("allgather", topo, 200)  # same bucket (pow2 ceiling)
    assert s.algo == "ring" and s.source == "measured"
    # other dtypes / buckets still fall back to the prior
    assert sel.choose("allgather", topo, 1 << 20).source == "prior"
    assert sel.choose("allgather", topo, 256, dtype="bfloat16").source == \
        "prior"
    assert sel.stats.measured == 1 and sel.stats.prior == 3
    assert 0 < sel.stats.measured_fraction < 1
    assert sel.stats.by_choice[("allgather", "ring")] == 1


def test_measured_entry_ignored_when_infeasible():
    """A measurement for an algorithm that is infeasible on this topology
    (recursive_doubling on non-pow2) must not be selected."""
    topo = Topology(3, 2)
    sel = Selector()
    sel.table.record(topo, "allreduce", "float32", 256,
                     "recursive_doubling", 1e-9)
    sel.table.record(topo, "allreduce", "float32", 256, "xla", 1e-3)
    s = sel.choose("allreduce", topo, 256)
    assert s.algo == "xla" and s.source == "measured"


# ---------------------------------------------------------------------------
# tuning table persistence
# ---------------------------------------------------------------------------


def test_tuning_table_json_round_trip(tmp_path):
    topo = Topology(4, 2, node_link="tpu_v5e_dcn", local_link="tpu_v5e_ici")
    t = TuningTable()
    t.record(topo, "allgather", "float32", 256, "pip_mcoll", 1.5e-6)
    t.record(topo, "allgather", "float32", 200, "ring", 2.5e-6)  # same bucket
    t.record(topo, "alltoall", "bfloat16", 4096, "xla", 9e-6)
    path = tmp_path / "table.json"
    t.save(path)
    t2 = TuningTable.load(path)
    assert t2.entries == t.entries
    assert len(t2) == len(t) == 3
    assert t2.lookup(topo, "allgather", "float32", 250) == {
        "pip_mcoll": 1.5e-6, "ring": 2.5e-6}
    # a selector loading the file resolves from measurement
    sel = Selector()
    sel.load_table(path)
    assert sel.choose("allgather", topo, 256).source == "measured"


def test_tuning_table_version_gate(tmp_path):
    with pytest.raises(ValueError):
        TuningTable.from_json({"version": 999, "entries": {}})


def test_tuning_table_keys_include_links():
    ici = Topology(4, 2, node_link="tpu_v5e_ici", local_link="tpu_v5e_ici")
    dcn = Topology(4, 2, node_link="tpu_v5e_dcn", local_link="tpu_v5e_ici")
    t = TuningTable()
    t.record(ici, "allgather", "float32", 256, "xla", 1e-6)
    assert t.lookup(dcn, "allgather", "float32", 256) is None, \
        "different link metadata must not share measurements"


def test_memo_invalidated_by_new_measurements():
    topo = Topology(4, 2)
    sel = Selector()
    first = sel.choose("allgather", topo, 256)
    assert first.source == "prior"
    for algo in autotune.candidates("allgather", topo):
        sel.table.record(topo, "allgather", "float32", 256, algo,
                         1e-6 if algo == "ring" else 1e-3)
    assert sel.choose("allgather", topo, 256).source == "measured"


# ---------------------------------------------------------------------------
# topology link metadata -> cost-model parameterisation
# ---------------------------------------------------------------------------


def test_net_for_composes_per_axis_links():
    topo = Topology(2, 256, node_link="tpu_v5e_dcn", local_link="tpu_v5e_ici")
    net = costmodel.net_for(topo)
    dcn, ici = costmodel.tpu_v5e_multipod(), costmodel.tpu_v5e_pod()
    assert net.alpha_inter == dcn.alpha_inter
    assert net.beta_inter == dcn.beta_inter
    assert net.alpha_intra == ici.alpha_intra
    assert net.beta_intra == ici.beta_intra
    assert "tpu_v5e_dcn" in net.name and "tpu_v5e_ici" in net.name


def test_net_for_defaults_and_overrides():
    assert costmodel.net_for(Topology(4, 2)).name == "tpu_v5e_dcn"
    override = costmodel.paper_cluster_pip()
    topo = Topology(4, 2, node_link=override, local_link=override)
    assert costmodel.net_for(topo) == override
    with pytest.raises(ValueError):
        costmodel.resolve_net("no_such_preset")


def test_from_mesh_derives_host_cpu_links():
    mesh = make_mesh((1, 1), ("node", "local"))
    topo = Topology.from_mesh(mesh)
    assert topo.link_names == ("host_cpu", "host_cpu")
    assert derive_link(mesh, "node", "inter") == "host_cpu"
    assert costmodel.net_for(topo).name == "host_cpu"
    # explicit links win over derivation
    topo2 = Topology.from_mesh(mesh, node_link="tpu_v5e_dcn")
    assert topo2.link_names == ("tpu_v5e_dcn", "host_cpu")


def test_back_compat_choose_and_tuning_table():
    topo = Topology(16, 16)
    net = costmodel.tpu_v5e_pod()
    algo, t = autotune.choose("allgather", topo, 256, net)
    assert algo == "pip_mcoll" and t > 0
    table = autotune.tuning_table("allgather", topo, net)
    assert set(table) == {2 ** i for i in range(4, 27)}
    assert all(isinstance(a, str) for a in table.values())


# ---------------------------------------------------------------------------
# the real thing: algo="auto" on an 8-device mesh matches every explicit
# algorithm, and calibration flips resolution to the measured table
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_auto_equivalence_and_calibration_8dev():
    out = run_check("auto_check.py", 8, 4, 2)
    assert "auto_check" in out and "OK" in out
