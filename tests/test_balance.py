"""Properties of the analytical model and the balanced-point solvers."""
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import balance, perfmodel as pm
from repro.core.tiling import TileConfig
from repro.kernels.matmul import LANE, SUBLANE, vmem_bytes
from repro.kernels.ops import GemmPlan


def test_single_core_respects_vmem_budget():
    for dt_in, dt_out in [
        (jnp.bfloat16, jnp.bfloat16),
        (jnp.int8, jnp.int8),
        (jnp.int8, jnp.int32),
        (jnp.float32, jnp.float32),
    ]:
        r = balance.solve_single_core(in_dtype=dt_in, out_dtype=dt_out)
        assert r.vmem <= pm.TPU_V5E.vmem_bytes
        assert r.compute_bound
        # paper Table 1: solutions use most of the budget (94-98% on L1)
        assert r.vmem >= 0.75 * pm.TPU_V5E.vmem_bytes


def test_single_core_small_budget_mirrors_paper_shape():
    """With an L1-like tiny budget the optimum is high-k, small-mn —
    the exact shape of the paper's Table 1 kernels."""
    r = balance.solve_single_core(
        in_dtype=jnp.bfloat16, vmem_budget=2 * 2**20
    )
    assert r.plan.bk >= r.plan.bm and r.plan.bk >= r.plan.bn


def test_balanced_never_worse_than_compute_optimal():
    """§5.2.1: the balanced kernel's end-to-end time must be <= the
    compute-optimal kernel's end-to-end time, across regimes."""
    for M, K, N in [(4096, 4096, 4096), (512, 8192, 512), (128, 4096, 65536)]:
        sc = balance.solve_single_core(in_dtype=jnp.bfloat16)
        t_sc = pm.estimate_gemm(
            pm.TPU_V5E, M, K, N, sc.plan.bm, sc.plan.bk, sc.plan.bn,
            in_dtype=jnp.bfloat16,
        ).t_total
        res = balance.solve_balanced(M, K, N, in_dtype=jnp.bfloat16)
        t_bal = min(s.t_total for s in res.steps)
        assert t_bal <= t_sc * (1 + 1e-9)


def test_inverse_relationship():
    """Eqs. 6-7: shrinking the output tile raises DRAM traffic, growing it
    lowers traffic but (under a fixed budget) shrinks bk and compute eff."""
    M = K = N = 4096
    est_small = pm.estimate_gemm(pm.TPU_V5E, M, K, N, 128, 2048, 128)
    est_big = pm.estimate_gemm(pm.TPU_V5E, M, K, N, 1024, 256, 1024)
    assert est_small.t_mem > est_big.t_mem          # traffic falls with bm,bn
    assert est_small.a_mem + est_small.b_mem > est_big.a_mem + est_big.b_mem


def test_effective_bw_saturates():
    """Fig. 6: effective BW grows with contiguity and saturates."""
    hw = pm.TPU_V5E
    bws = [pm.effective_bw(hw, r) for r in (64, 256, 1024, 4096, 16384)]
    assert all(b2 > b1 for b1, b2 in zip(bws, bws[1:]))
    assert bws[-1] / bws[-2] < 1.02   # knee: marginal gain < 2%
    assert bws[-1] < hw.hbm_bw


def test_colmajor_b_beats_rowmajor_for_skinny_n():
    """§5.2.3: B column-major reads bk-long runs, row-major only bn-long;
    for small bn the col-major layout wins on memory time."""
    bt_row = pm.block_times(pm.TPU_V5E, 256, 2048, 128, b_layout="row")
    bt_col = pm.block_times(pm.TPU_V5E, 256, 2048, 128, b_layout="col")
    assert bt_col.t_b < bt_row.t_b


@settings(max_examples=30, deadline=None)
@given(
    bk=st.sampled_from([256, 512, 1024, 2048]),
    bmn=st.sampled_from([128, 256, 512, 1024]),
)
def test_property_estimate_positive(bk, bmn):
    est = pm.estimate_gemm(pm.TPU_V5E, 4096, 4096, 4096, bmn, bk, bmn)
    assert est.t_comp > 0 and est.t_mem > 0
    assert 0 < est.eff <= 1.0


@settings(max_examples=30, deadline=None)
@given(
    M=st.integers(1, 10000), K=st.integers(1, 10000), N=st.integers(1, 10000),
)
def test_property_tileconfig_grid_covers_problem(M, K, N):
    cfg = TileConfig(M=M, K=K, N=N, plan=GemmPlan(256, 512, 256),
                     m_rows=4, n_cols=8).validate()
    Mp, Kp, Np = cfg.padded
    gi, gj, gk = cfg.grid
    assert Mp >= M and Kp >= K and Np >= N
    assert gi * 256 * 4 == Mp and gj * 256 * 8 == Np and gk * 512 == Kp
    assert 0 <= cfg.padding_waste < 1


def test_balance_iteration_terminates_at_knee():
    """§4.5.2 with patience: the walk stops after <=3 consecutive
    non-improving probes and returns the best recorded step."""
    res = balance.solve_balanced(1024, 8192, 1024, in_dtype=jnp.bfloat16)
    ts = [s.t_total for s in res.steps]
    assert res.plan in [s.plan for s in res.steps]
    assert min(ts) == [s.t_total for s in res.steps
                       if s.plan == res.plan][0]
    # the tail contains at most 3 probes past the best point
    best_idx = ts.index(min(ts))
    run = 0
    for t in ts[best_idx + 1:]:
        run = run + 1 if t > min(ts) else 0
    assert run <= 3


def test_balance_result_reports_actual_balance():
    """`balanced` means the chosen point's t_comp and t_mem are within the
    tolerance — not merely that the walk recorded steps."""
    def result_for(t_comp, t_mem):
        plan = GemmPlan(256, 512, 256)
        step = balance.BalanceStep(
            plan=plan, t_comp=t_comp, t_mem=t_mem,
            t_total=max(t_comp, t_mem), tops=1.0)
        return balance.BalanceResult(plan=plan, steps=[step], tops=1.0)

    assert result_for(1.0, 0.9).balanced
    assert result_for(0.9, 1.0).balanced
    assert not result_for(1.0, 0.4).balanced          # memory-starved
    assert not result_for(0.4, 1.0).balanced          # memory-bound
    assert result_for(1.0, 0.4).is_balanced(tol=0.8)  # tolerance is a knob
    # a result whose plan matches no recorded step cannot claim balance
    orphan = balance.BalanceResult(
        plan=GemmPlan(128, 128, 128),
        steps=result_for(1.0, 1.0).steps, tops=1.0)
    assert orphan.chosen_step is None and not orphan.balanced


def test_balanced_property_consistent_with_chosen_step():
    """On real solver output the property must agree with the recorded
    times of the step the returned plan came from."""
    for M, K, N in [(4096, 4096, 4096), (64, 8192, 28672)]:
        res = balance.solve_exhaustive(M, K, N, in_dtype=jnp.bfloat16)
        s = res.chosen_step
        assert s is not None and s.plan == res.plan
        hi, lo = max(s.t_comp, s.t_mem), min(s.t_comp, s.t_mem)
        assert res.balanced == ((hi - lo) / hi <= 0.25)


def test_roofline_terms():
    rt = pm.roofline_terms(
        pm.TPU_V5E, hlo_flops=1e15, hlo_bytes=1e12, collective_bytes=1e11,
        chips=256,
    )
    assert rt.dominant in ("compute", "memory", "collective")
    assert rt.bound == max(rt.compute, rt.memory, rt.collective)
    # hand-check one term: 1e15 / (256 * 197e12)
    assert abs(rt.compute - 1e15 / (256 * 197e12)) < 1e-12
