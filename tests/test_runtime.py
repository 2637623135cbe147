"""FIBER runtime semantics — paper §3.1–3.3, §4, §6.3."""
import time

import pytest

from repro.core import (OAT_ALL, OAT_DYNAMIC, OAT_INSTALL, OAT_STATIC,
                        Fitting, OATHierarchyError,
                        OATMissingBasicParamError, OATPriorityError,
                        ParamStore, Varied)
from repro.core import paramfile
from repro.core.directives import (dynamic_select, install_define,
                                   install_unroll, static_select,
                                   static_unroll)


# --------------------------------------------------------------------------
# parameter store / Fig. 4 hierarchy
# --------------------------------------------------------------------------

class TestHierarchy:
    def test_install_visible_downstream(self):
        st = ParamStore()
        st.set_pp("CacheSize", 64, "install")
        assert st.get("CacheSize", "install") == 64
        assert st.get("CacheSize", "static") == 64
        assert st.get("CacheSize", "dynamic") == 64

    def test_static_not_visible_to_install(self):
        st = ParamStore()
        st.set_pp("X", 1, "static")
        with pytest.raises(OATHierarchyError):
            st.get("X", "install")

    def test_dynamic_only_visible_to_dynamic(self):
        st = ParamStore()
        st.set_pp("Y", 2, "dynamic")
        assert st.get("Y", "dynamic") == 2
        with pytest.raises(OATHierarchyError):
            st.get("Y", "static")

    def test_feedback_model_exception(self):
        """§3.1 footnote: with the FIBER feedback model, static may read
        dynamic-determined parameters."""
        st = ParamStore(feedback=True)
        st.set_pp("Y", 2, "dynamic")
        assert st.get("Y", "static") == 2

    def test_bps_visible_everywhere(self):
        st = ParamStore()
        st.set_bp("n", 1024)
        for phase in ("install", "static", "dynamic"):
            assert st.get("n", phase) == 1024


# --------------------------------------------------------------------------
# execution priority (§3.2) + BP guards (§4.2.2)
# --------------------------------------------------------------------------

def _add_regions(ctx):
    @install_define(ctx, name="SetCacheParam",
                    params=[("CacheSize", "out"), ("CacheLine", "out")])
    def set_cache():
        return {"CacheSize": 64, "CacheLine": 8}

    @static_unroll(ctx, name="MyMatMul", varied=Varied(("i", "j"), 1, 4),
                   params=["bp n"])
    def my_matmul(i=1, j=1, n=64):
        return lambda: (i - 2) ** 2 + (j - 3) ** 2 + 0.0

    return set_cache, my_matmul


def test_static_before_install_raises(ctx_with_bps):
    _add_regions(ctx_with_bps)
    with pytest.raises(OATPriorityError):
        ctx_with_bps.OAT_ATexec(OAT_STATIC, None)


def test_install_without_bps_raises(ctx):
    _add_regions(ctx)
    with pytest.raises(OATMissingBasicParamError):
        ctx.OAT_ATexec(OAT_INSTALL, None)


def test_dynamic_before_static_raises(ctx_with_bps):
    _add_regions(ctx_with_bps)
    ctx_with_bps.OAT_ATexec(OAT_INSTALL, None)
    with pytest.raises(OATPriorityError):
        ctx_with_bps.OAT_ATexec(OAT_DYNAMIC, None)


def test_full_priority_sequence_ok(ctx_with_bps):
    _add_regions(ctx_with_bps)
    ctx_with_bps.OAT_ATexec(OAT_INSTALL, None)
    ctx_with_bps.OAT_ATexec(OAT_STATIC, None)
    ctx_with_bps.OAT_ATexec(OAT_DYNAMIC, None)


# --------------------------------------------------------------------------
# install-time define (Sample 2) + parameter file output
# --------------------------------------------------------------------------

def test_install_define_writes_param_file(ctx_with_bps, tmp_path):
    _add_regions(ctx_with_bps)
    ctx_with_bps.OAT_ATexec(OAT_INSTALL, None)
    nodes = paramfile.load_file(
        paramfile.param_path(str(tmp_path), "install"))
    rec = next(n for n in nodes if n.name == "SetCacheParam")
    assert rec.child_value("CacheSize") == 64
    assert rec.child_value("CacheLine") == 8
    # visible downstream (FIBER)
    assert ctx_with_bps.store.get("CacheSize", "static") == 64


# --------------------------------------------------------------------------
# before-execute-time AT (Sample 4): BP sweep + nested records
# --------------------------------------------------------------------------

def _bp_dependent_factory(region, bp_env):
    def measure(asg):
        tgt = bp_env.get("OAT_PROBSIZE", 1024) // 1024
        return (asg.get("MyMatMul_I", 0) - tgt) ** 2 \
            + (asg.get("MyMatMul_J", 0) - 3) ** 2
    return measure


@pytest.fixture
def tuned_static(ctx_with_bps):
    _add_regions(ctx_with_bps)
    ctx_with_bps._executor_factory = _bp_dependent_factory
    ctx_with_bps.OAT_ATexec(OAT_INSTALL, None)
    ctx_with_bps.OAT_ATexec(OAT_STATIC, None)
    return ctx_with_bps


def test_static_records_per_probsize(tuned_static):
    nodes = paramfile.load_file(
        paramfile.param_path(tuned_static.workdir, "static"))
    mm = next(n for n in nodes if n.name == "MyMatMul")
    for size, want_i in ((1024, 1), (2048, 2), (3072, 3)):
        g = mm.keyed_child("OAT_PROBSIZE", size)
        assert g is not None
        assert g.child_value("MyMatMul_I") == want_i
        assert g.child_value("MyMatMul_J") == 3


def test_static_pp_interpolates_nonsample_points(tuned_static):
    """OAT_BPsetCDF semantics: non-sample problem sizes are inferred."""
    assert tuned_static.static_pp("MyMatMul", "MyMatMul_I", 2048) == 2
    tuned_static.OAT_BPsetCDF("n", "least-squares 1")
    v = tuned_static.static_pp("MyMatMul", "MyMatMul_I", 2560)
    assert v in (2, 3)


def test_search_counts_logged(tuned_static):
    # 4x4 joint exhaustive per BP point (default for unroll)
    assert tuned_static.search_log["MyMatMul"] == 16


# --------------------------------------------------------------------------
# parameter collision (§6.3)
# --------------------------------------------------------------------------

def test_collision_force_sets_user_value(ctx_with_bps, tmp_path):
    _add_regions(ctx_with_bps)
    pin = paramfile.Node("MyMatMul")
    pin.set("MyMatMul_I", 9)
    paramfile.save_file(
        paramfile.param_path(str(tmp_path), "static", user=True), [pin])
    ctx_with_bps._executor_factory = _bp_dependent_factory
    ctx_with_bps.OAT_ATexec(OAT_INSTALL, None)
    ctx_with_bps.OAT_ATexec(OAT_STATIC, None)
    assert ("MyMatMul", "MyMatMul_I", 9) in ctx_with_bps.collisions
    nodes = paramfile.load_file(
        paramfile.param_path(str(tmp_path), "static"))
    mm = next(n for n in nodes if n.name == "MyMatMul")
    assert mm.keyed_child("OAT_PROBSIZE", 1024) \
        .child_value("MyMatMul_I") == 9      # user value force-set


# --------------------------------------------------------------------------
# run-time AT: dynamic select (Sample 6), DynPerfThis (Sample 7), ATdel
# --------------------------------------------------------------------------

def _make_select(ctx):
    sel = dynamic_select(ctx, name="PrecondSelect",
                         params=["in eps", "in iter"],
                         according="min (eps) .and. condition (iter < 5)")

    @sel.alternative()
    def p1():
        return {"eps": 0.5, "iter": 3}

    @sel.alternative()
    def p2():
        return {"eps": 0.1, "iter": 9}     # best eps, violates iter < 5

    @sel.alternative()
    def p3():
        return {"eps": 0.3, "iter": 2}

    return sel.finalize()


def test_dynamic_select_sample6(ctx):
    _make_select(ctx)
    ctx.OAT_ATexec(OAT_DYNAMIC, None)
    for _ in range(3):
        ctx.execute("PrecondSelect")
    st = ctx.dynamic_state["PrecondSelect"]
    assert st.committed == 2     # p3: min eps among those with iter < 5
    # subsequent calls run the winner, no more tuning
    out = ctx.execute("PrecondSelect")
    assert out == {"eps": 0.3, "iter": 2}


def test_dyn_perf_this_runs_optimised_without_tuning(ctx):
    """Sample 7 semantics: OAT_DynPerfThis executes with optimised PPs and
    performs no parameter tuning."""
    _make_select(ctx)
    ctx.OAT_ATexec(OAT_DYNAMIC, None)
    for _ in range(3):
        ctx.execute("PrecondSelect")
    n_before = len(ctx.dynamic_state["PrecondSelect"].tried)
    out = ctx.OAT_DynPerfThis("PrecondSelect")
    assert out == {"eps": 0.3, "iter": 2}
    assert len(ctx.dynamic_state["PrecondSelect"].tried) == n_before


def test_dynamic_clock_stops_when_the_output_is_ready(ctx):
    """A candidate is timed until its output is ready, not until it was
    dispatched: JAX returns before the device is done."""
    class Dispatched:
        """An output that is ready 50 ms after the call returned."""

        ready = False

        def block_until_ready(self):
            time.sleep(0.05)
            self.ready = True
            return self

    sel = dynamic_select(ctx, name="AsyncSelect", params=[])
    issued = []

    @sel.alternative()
    def enqueue_only():
        issued.append(Dispatched())
        return issued[-1]

    @sel.alternative()
    def synchronous():
        time.sleep(0.02)
        return 0

    sel.finalize()
    ctx.OAT_ATexec(OAT_DYNAMIC, None)
    for _ in range(2):
        ctx.execute("AsyncSelect")
    st = ctx.dynamic_state["AsyncSelect"]
    assert issued[0].ready
    assert st.tried[0] >= 0.05 > st.tried[1] >= 0.02
    assert st.committed == 1


def test_atdel_removes_candidate(ctx_with_bps):
    _add_regions(ctx_with_bps)
    ctx_with_bps.OAT_ATdel("OAT_InstallRoutines", "SetCacheParam")
    ctx_with_bps.OAT_ATexec(OAT_INSTALL, None)
    nodes = paramfile.load_file(
        paramfile.param_path(ctx_with_bps.workdir, "install"))
    assert not any(n.name == "SetCacheParam" for n in nodes)


def test_install_init_allows_rerun(ctx_with_bps):
    _add_regions(ctx_with_bps)
    ctx_with_bps.OAT_ATexec(OAT_INSTALL, None)
    assert ctx_with_bps.store.entry("CacheSize") is not None
    ctx_with_bps.OAT_ATInstallInit("OAT_InstallRoutines")
    assert ctx_with_bps.store.entry("CacheSize") is None
    ctx_with_bps.OAT_ATexec(OAT_INSTALL, None)   # runs again cleanly
    assert ctx_with_bps.store.entry("CacheSize").value == 64


def test_oat_all_runs_phases_in_order(ctx_with_bps):
    _add_regions(ctx_with_bps)
    ctx_with_bps._executor_factory = _bp_dependent_factory
    ctx_with_bps.OAT_ATexec(OAT_ALL, None)
    assert ctx_with_bps.phase_ran["install"]
    assert ctx_with_bps.phase_ran["static"]
    assert ctx_with_bps.phase_ran["dynamic"]


# --------------------------------------------------------------------------
# estimated-cost select (Sample 5)
# --------------------------------------------------------------------------

def test_static_select_according_estimated(ctx_with_bps):
    """Sample 5: selection by user cost expressions over BPs + install
    parameters, Fortran syntax included."""

    @install_define(ctx_with_bps, name="SetCacheParam",
                    params=[("CacheSize", "out")])
    def set_cache():
        return {"CacheSize": 64}

    sel = static_select(
        ctx_with_bps, name="ATfromCacheSize",
        params=["in CacheSize", "bp OAT_PROBSIZE", "bp OAT_NUMPROCS"])
    sel.alternative(according=(
        "estimated 2.0d0*CacheSize*OAT_PROBSIZE*OAT_PROBSIZE"
        " / (3.0d0*OAT_NUMPROCS)"))(lambda: "process1")
    sel.alternative(according=(
        "estimated 4.0d0*CacheSize*OAT_PROBSIZE"
        "*dlog(OAT_PROBSIZE) / (2.0d0*OAT_NUMPROCS)"))(lambda: "process2")
    sel.finalize()

    ctx_with_bps.OAT_ATexec(OAT_INSTALL, None)
    ctx_with_bps.OAT_ATexec(OAT_STATIC, ["ATfromCacheSize"])
    # for OAT_PROBSIZE >= 1024: n^2/3 >> 2n log n, so process2 wins
    nodes = paramfile.load_file(
        paramfile.param_path(ctx_with_bps.workdir, "static"))
    rec = next(n for n in nodes if n.name == "ATfromCacheSize")
    g = rec.keyed_child("OAT_PROBSIZE", 1024)
    assert g.child_value("ATfromCacheSize_SELECT") == 1
