"""Boundary behaviour of the §4.2/§4.3 heuristics, exactly at the paper's
thresholds, and proof that the plan layer honors every decision."""
import dataclasses

import numpy as np
import pytest

from repro.core import alto, heuristics, plan as plan_mod
from repro.core.heuristics import (BUFFERED_ACCUM_COST, HIGH_REUSE,
                                   MEDIUM_REUSE, PiPolicy, Traversal)
from repro.sparse import synthetic


def _tile(rows, cols, db=4):
    """VMEM bytes of a (rows, cols) array in (8·4/db, 128) tiles."""
    sub = 32 // db
    return -(-rows // sub) * sub * (-(-cols // 128) * 128) * db


def _words(meta, bm, n_staged):
    """Double-buffered index-word blocks + the coordinate staging tile."""
    return 2 * meta.enc.n_words * bm * 4 + _tile(n_staged, bm)


def _others(meta, mode, cols, db=4):
    return sum(_tile(I, cols, db) for m, I in enumerate(meta.dims)
               if m != mode)


def _meta_with_reuse(reuse_per_mode):
    x = synthetic.uniform_tensor((16, 12, 8)[:len(reuse_per_mode)],
                                 200, seed=0)
    at = alto.build(x, n_partitions=2)
    return dataclasses.replace(at.meta,
                               fiber_reuse=tuple(reuse_per_mode))


class TestClassifyReuseBoundaries:
    def test_exactly_high_threshold_is_medium(self):
        # classification is strict-greater at HIGH_REUSE (Table 1)
        assert heuristics.classify_reuse(HIGH_REUSE) == "medium"
        assert heuristics.classify_reuse(np.nextafter(HIGH_REUSE,
                                                      np.inf)) == "high"

    def test_exactly_medium_threshold_is_medium(self):
        # ...but inclusive at MEDIUM_REUSE
        assert heuristics.classify_reuse(MEDIUM_REUSE) == "medium"
        assert heuristics.classify_reuse(np.nextafter(MEDIUM_REUSE,
                                                      -np.inf)) == "limited"

    def test_tensor_class_takes_worst_mode(self):
        meta = _meta_with_reuse((HIGH_REUSE + 1, MEDIUM_REUSE, 100.0))
        assert heuristics.tensor_reuse_class(meta) == "medium"
        meta = _meta_with_reuse((100.0, MEDIUM_REUSE - 1, 100.0))
        assert heuristics.tensor_reuse_class(meta) == "limited"


class TestTraversalBoundary:
    def test_exactly_buffered_cost_goes_oriented(self):
        """Recursive pays off only STRICTLY above the 4-memory-op cost."""
        meta = _meta_with_reuse((BUFFERED_ACCUM_COST,) * 3)
        for mode in range(3):
            assert heuristics.choose_traversal(meta, mode) \
                is Traversal.OUTPUT_ORIENTED

    def test_epsilon_above_goes_recursive(self):
        above = np.nextafter(BUFFERED_ACCUM_COST, np.inf)
        meta = _meta_with_reuse((above,) * 3)
        for mode in range(3):
            assert heuristics.choose_traversal(meta, mode) \
                is Traversal.RECURSIVE

    def test_per_mode_independence(self):
        meta = _meta_with_reuse((BUFFERED_ACCUM_COST + 1,
                                 BUFFERED_ACCUM_COST,
                                 BUFFERED_ACCUM_COST - 1))
        got = [heuristics.choose_traversal(meta, m) for m in range(3)]
        assert got == [Traversal.RECURSIVE, Traversal.OUTPUT_ORIENTED,
                       Traversal.OUTPUT_ORIENTED]


class TestPiPolicyBoundary:
    def test_factor_bytes_exactly_at_budget_stays_otf(self):
        """PRE requires factors STRICTLY over fast memory (§4.3)."""
        meta = _meta_with_reuse((1.0, 1.0, 1.0))        # limited reuse
        rank, vb = 4, 4
        budget = sum(I * rank * vb for I in meta.dims)
        assert heuristics.choose_pi_policy(
            meta, rank, value_bytes=vb, fast_mem_bytes=budget) \
            is PiPolicy.OTF
        assert heuristics.choose_pi_policy(
            meta, rank, value_bytes=vb, fast_mem_bytes=budget - 1) \
            is PiPolicy.PRE

    def test_medium_reuse_never_pre(self):
        meta = _meta_with_reuse((MEDIUM_REUSE,) * 3)    # medium, not limited
        assert heuristics.choose_pi_policy(
            meta, 64, fast_mem_bytes=1) is PiPolicy.OTF


class TestPlanHonorsHeuristics:
    @pytest.mark.parametrize("reuse", [
        (BUFFERED_ACCUM_COST, BUFFERED_ACCUM_COST + 2, 1.0),
        (100.0, 100.0, 100.0),
        (1.0, 1.0, 1.0),
    ])
    def test_traversal_decisions_copied_into_plan(self, reuse):
        """The plan honors the family choice; an output-oriented mode is
        then refined to one-hot merge vs scratch carry by the traffic
        model (`choose_oriented_variant`), which the plan must copy."""
        meta = _meta_with_reuse(reuse)
        plan = plan_mod.make_plan(meta, 8)
        for mode in range(3):
            family = heuristics.choose_traversal(meta, mode)
            got = plan.modes[mode].traversal
            if family is Traversal.RECURSIVE:
                assert got is Traversal.RECURSIVE
            else:
                assert heuristics.is_oriented(got)
                assert got is heuristics.choose_oriented_variant(
                    meta, mode, 8,
                    carry_feasible=plan_mod.carry_fits_vmem(meta, mode, 8))

    def test_pi_policy_copied_into_plan(self):
        meta = _meta_with_reuse((1.0, 1.0, 1.0))
        tight = plan_mod.make_plan(meta, 8, fast_mem_bytes=1)
        roomy = plan_mod.make_plan(meta, 8)
        assert tight.pi_policy is heuristics.choose_pi_policy(
            meta, 8, fast_mem_bytes=1)
        assert tight.pi_policy is PiPolicy.PRE
        assert roomy.pi_policy is PiPolicy.OTF

    def test_views_built_only_for_oriented_modes(self):
        meta = _meta_with_reuse((100.0, 1.0, 100.0))
        x = synthetic.uniform_tensor((16, 12, 8), 200, seed=0)
        at = alto.build(x, n_partitions=2)
        at = alto.AltoTensor(meta, at.words, at.values, at.part_start,
                             at.part_end)
        plan = plan_mod.make_plan(meta, 4)
        views = plan_mod.build_views(at, plan)
        assert sorted(views) == [1]

    def test_cpapr_reports_plan_decisions(self):
        x, _ = synthetic.lowrank_count((12, 10, 8), rank=2,
                                       nnz_target=250, seed=5)
        at = alto.build(x, n_partitions=2)
        from repro.core import cpapr
        plan = plan_mod.make_plan(at.meta, 2, backend="reference")
        res = cpapr.cp_apr(at, rank=2, seed=1,
                           params=cpapr.CpaprParams(k_max=1), plan=plan)
        assert res.traversals == list(plan.traversals())
        assert res.pi_policy == plan.pi_policy.value


class TestPhiVmemFootprint:
    """Exact byte accounting of the Φ-specific VMEM model — the
    ROADMAP-flagged gap: the fused Φ kernel keeps the full-rank B
    (I_mode × R) resident per grid step plus the gathered block B rows,
    which the MTTKRP-shaped model never budgeted."""

    def _meta(self, dims=(64, 48, 32), nnz=2000, L=4):
        x = synthetic.uniform_tensor(dims, nnz, seed=0)
        return alto.build(x, n_partitions=L).meta

    def test_phi_oriented_exact_bytes_otf(self):
        meta = self._meta()
        mode, bm, R, db = 1, 64, 8, 4
        N = meta.enc.ndim
        want = (_words(meta, bm, N - 1)         # words + staging
                + 2 * bm * 4                    # segment-id blocks
                + bm * bm * (4 + db)            # one-hot + its iota
                + _tile(meta.dims[mode], R)     # RESIDENT full-rank B
                + _tile(bm, R)                  # contribution tile
                + 2 * _tile(bm, R)              # segment-sum output
                + _others(meta, mode, R))       # resident other factors
        got = plan_mod.phi_oriented_vmem_bytes(meta, mode, bm, R, db)
        assert got == want

    def test_phi_oriented_pre_streams_pi_instead_of_factors(self):
        meta = self._meta()
        mode, bm, R, db = 0, 128, 16, 4
        otf = plan_mod.phi_oriented_vmem_bytes(meta, mode, bm, R, db,
                                               pre_pi=False)
        pre = plan_mod.phi_oriented_vmem_bytes(meta, mode, bm, R, db,
                                               pre_pi=True)
        # PRE swaps the words, staging and resident factors for a
        # double-buffered (block_m, R) Π tile
        assert otf - pre == (_words(meta, bm, meta.enc.ndim - 1)
                             + _others(meta, mode, R) - 2 * _tile(bm, R))

    def test_phi_recursive_exact_bytes_otf(self):
        meta = self._meta(L=4)
        mode, R, db = 2, 8, 4
        bm = plan_mod.MIN_BLOCK_M
        T = meta.temp_rows[mode]
        want = (_words(meta, bm, meta.enc.ndim)  # words + all-mode staging
                + 2 * _tile(T, R)               # partition Temp output
                + _tile(meta.dims[mode], R)     # RESIDENT full-rank B
                + _others(meta, mode, R)        # resident other factors
                + 3 * _tile(bm, R))             # Φ, B-row, value scratch
        got = plan_mod.phi_recursive_vmem_bytes(meta, mode, R, db)
        assert got == want

    def test_resident_b_scales_with_mode_dim_not_block(self):
        """The gap term: growing I_mode must grow the Φ footprint even
        with every blocking knob frozen (B is resident whole)."""
        small = self._meta(dims=(64, 48, 32))
        big = self._meta(dims=(4096, 48, 32))
        R, bm = 16, 64
        delta = (plan_mod.phi_oriented_vmem_bytes(big, 0, bm, R)
                 - plan_mod.phi_oriented_vmem_bytes(small, 0, bm, R))
        assert delta >= (4096 - 64) * R * 4     # at least the B rows

    def test_phi_footprint_constrains_plan_block_m(self):
        """On a big mode with a tight budget the Φ-aware choice must pick
        a smaller block than the MTTKRP-only model would."""
        meta = self._meta(dims=(2048, 16, 12), nnz=3000)
        R = 16
        budget = plan_mod.phi_oriented_vmem_bytes(
            meta, 0, plan_mod.MAX_BLOCK_M, R) - 1
        assert plan_mod.phi_constraint_active(meta, 0, R,
                                              vmem_limit=budget)
        rb = plan_mod.choose_rank_block_oriented(meta, 0, R,
                                                 vmem_limit=budget)
        mttkrp_only = plan_mod.choose_block_m(meta, 0, rb,
                                              vmem_limit=budget)
        phi_aware = plan_mod.choose_block_m(meta, 0, rb, vmem_limit=budget,
                                            rank=R)
        assert phi_aware < mttkrp_only
        assert plan_mod.phi_oriented_vmem_bytes(meta, 0, phi_aware, R) \
            <= budget

    def test_unsatisfiable_phi_budget_does_not_throttle_mttkrp(self):
        """When the resident-B term alone overflows the budget at every
        block size, Φ spills regardless — the vacuous constraint must
        not drag the MTTKRP kernel's block down to the minimum."""
        meta = self._meta(dims=(65536, 24, 16), nnz=3000)
        R = 64
        # budget below Φ's floor but roomy for MTTKRP tiles
        budget = plan_mod.phi_oriented_vmem_bytes(
            meta, 0, plan_mod.MIN_BLOCK_M, R) - 1
        assert not plan_mod.phi_constraint_active(meta, 0, R,
                                                  vmem_limit=budget)
        rb = plan_mod.choose_rank_block_oriented(meta, 0, R,
                                                 vmem_limit=budget)
        mttkrp_only = plan_mod.choose_block_m(meta, 0, rb,
                                              vmem_limit=budget)
        phi_aware = plan_mod.choose_block_m(meta, 0, rb, vmem_limit=budget,
                                            rank=R)
        assert phi_aware == mttkrp_only > plan_mod.MIN_BLOCK_M
        # and the candidate space keeps those larger blocks visible
        # (at the same rank tile; smaller tiles may go larger still)
        cands = plan_mod.candidate_mode_plans(meta, 0, R,
                                              vmem_limit=budget)
        same_rb = [c for c in cands
                   if c.traversal is heuristics.Traversal.OUTPUT_ORIENTED
                   and c.r_block == rb]
        assert max(c.block_m for c in same_rb) == mttkrp_only

    def test_mode_plan_records_phi_footprint(self):
        meta = self._meta()
        plan = plan_mod.make_plan(meta, 8)
        pre = plan.pi_policy is heuristics.PiPolicy.PRE
        assert Traversal.ORIENTED_CARRY in {mp.traversal
                                            for mp in plan.modes}
        for mp in plan.modes:
            if mp.traversal is Traversal.OUTPUT_ORIENTED:
                want = plan_mod.phi_oriented_vmem_bytes(
                    meta, mp.mode, mp.block_m, plan.rank, pre_pi=pre)
            elif mp.traversal is Traversal.ORIENTED_CARRY:
                want = plan_mod.phi_oriented_carry_vmem_bytes(
                    meta, mp.mode, mp.block_m, plan.rank, pre_pi=pre)
            else:
                want = plan_mod.phi_recursive_vmem_bytes(
                    meta, mp.mode, plan.rank, pre_pi=pre)
            assert mp.phi_vmem_bytes == want > 0


class TestCarryVmemFootprint:
    """Exact byte accounting of the scratch-carry kernel's VMEM model:
    no (block_m, block_m) one-hot, but the (I_mode, r_block) output tile
    and the carry scratch are resident across the whole sequential scan."""

    def _meta(self, dims=(64, 48, 32), nnz=2000, L=4):
        x = synthetic.uniform_tensor(dims, nnz, seed=0)
        return alto.build(x, n_partitions=L).meta

    def test_carry_exact_bytes(self):
        meta = self._meta()
        mode, bm, rb, db = 1, 64, 8, 4
        want = (_words(meta, bm, meta.enc.ndim - 1)  # words + staging
                + _tile(bm, rb)                 # contribution tile
                + 2 * _tile(meta.dims[mode], rb)  # RESIDENT output tile
                + 4 * _tile(1, rb)              # carry in/out blocks
                + _others(meta, mode, rb))      # resident other factors
        got = plan_mod.oriented_carry_vmem_bytes(meta, mode, bm, rb, db)
        assert got == want

    def test_phi_carry_exact_bytes_otf(self):
        meta = self._meta()
        mode, bm, R, db = 0, 32, 8, 4
        want = (_words(meta, bm, meta.enc.ndim - 1)  # words + staging
                + _tile(meta.dims[mode], R)     # RESIDENT full-rank B
                + _tile(bm, R)                  # contribution tile
                + 2 * _tile(meta.dims[mode], R)  # RESIDENT output block
                + 4 * _tile(1, R)               # carry in/out blocks
                + _others(meta, mode, R))       # resident other factors
        got = plan_mod.phi_oriented_carry_vmem_bytes(meta, mode, bm, R, db)
        assert got == want

    def test_phi_carry_pre_streams_pi_instead_of_factors(self):
        meta = self._meta()
        mode, bm, R, db = 0, 128, 16, 4
        otf = plan_mod.phi_oriented_carry_vmem_bytes(meta, mode, bm, R, db,
                                                     pre_pi=False)
        pre = plan_mod.phi_oriented_carry_vmem_bytes(meta, mode, bm, R, db,
                                                     pre_pi=True)
        assert otf - pre == (_words(meta, bm, meta.enc.ndim - 1)
                             + _others(meta, mode, R) - 2 * _tile(bm, R))

    def test_no_onehot_term(self):
        """Doubling block_m must grow the carry footprint linearly (the
        one-hot kernel grows quadratically) — the whole point of the
        rewrite."""
        meta = self._meta()
        rb = 4
        c = [plan_mod.oriented_carry_vmem_bytes(meta, 0, bm, rb)
             for bm in (128, 256, 512)]
        assert c[2] - c[1] == 2 * (c[1] - c[0])     # linear in block_m
        o = [plan_mod.oriented_vmem_bytes(meta, 0, bm, rb)
             for bm in (128, 256, 512)]
        assert o[2] - o[1] > 2 * (o[1] - o[0])      # quadratic one-hot

    def test_resident_output_scales_with_mode_dim(self):
        small = self._meta(dims=(64, 48, 32))
        big = self._meta(dims=(4096, 48, 32))
        rb, bm = 8, 64
        delta = (plan_mod.oriented_carry_vmem_bytes(big, 0, bm, rb)
                 - plan_mod.oriented_carry_vmem_bytes(small, 0, bm, rb))
        assert delta >= (4096 - 64) * rb * 4

    def test_carry_feasibility_gate(self):
        """carry_fits_vmem is a hard routing gate: below the resident
        output's floor the static plan must route the one-hot merge."""
        meta = self._meta()
        floor = plan_mod.oriented_carry_vmem_bytes(
            meta, 0, plan_mod.MIN_BLOCK_M, 1)
        assert plan_mod.carry_fits_vmem(meta, 0, 8, vmem_limit=floor)
        assert not plan_mod.carry_fits_vmem(meta, 0, 8,
                                            vmem_limit=floor - 1)
        mp = plan_mod.static_mode_plan(meta, 0, 8, vmem_limit=floor - 1)
        assert mp.traversal is Traversal.OUTPUT_ORIENTED
        # and the candidate space hard-gates carry candidates too
        cands = plan_mod.candidate_mode_plans(meta, 0, 8,
                                              vmem_limit=floor - 1)
        assert Traversal.ORIENTED_CARRY not in {c.traversal for c in cands}


class TestOrientedVariantTrafficBoundary:
    """The one-hot-vs-carry refinement is a pure HBM-traffic comparison:
    carry wins iff 2·I_n·R < 2·M·R + M·4/db + I_n·R (in elements)."""

    def _meta_with_dims(self, dims, nnz):
        x = synthetic.uniform_tensor(dims, nnz, seed=0)
        at = alto.build(x, n_partitions=2)
        return dataclasses.replace(at.meta, fiber_reuse=(1.0,) * len(dims))

    def test_traffic_terms_exact(self):
        meta = self._meta_with_dims((40, 30, 20), 500)
        R, db = 16, 4
        M = heuristics.stream_len(meta)
        assert heuristics.oriented_merge_traffic_bytes(meta, 0, R, db) \
            == 2 * M * R * db + M * 4 + meta.dims[0] * R * db
        assert heuristics.carry_traffic_bytes(meta, 0, R, db) \
            == 2 * meta.dims[0] * R * db

    def test_nnz_heavy_mode_goes_carry(self):
        meta = self._meta_with_dims((40, 30, 20), 5000)   # stream >> I_0
        assert heuristics.choose_oriented_variant(meta, 0, 16) \
            is heuristics.Traversal.ORIENTED_CARRY

    def test_hyper_sparse_long_mode_stays_onehot(self):
        # I_0 dwarfs the stream: resident-output traffic loses
        meta = self._meta_with_dims((100_000, 4, 3), 64)
        assert heuristics.choose_oriented_variant(meta, 0, 16) \
            is heuristics.Traversal.OUTPUT_ORIENTED

    def test_infeasible_carry_never_chosen(self):
        meta = self._meta_with_dims((40, 30, 20), 5000)
        assert heuristics.choose_oriented_variant(
            meta, 0, 16, carry_feasible=False) \
            is heuristics.Traversal.OUTPUT_ORIENTED


class TestChunkByteModels:
    """Byte-exact accounting of the out-of-core (HBM) chunk models and
    the chunk-size choice they drive — mirrors TestCarryVmemFootprint:
    every term is re-derived here by hand, so a silent model edit goes
    red, not just a routing flip."""

    def _meta(self, dims=(64, 48, 32), nnz=2000, L=4):
        x = synthetic.uniform_tensor(dims, nnz, seed=0)
        return alto.build(x, n_partitions=L).meta

    def test_stream_elem_exact_bytes(self):
        meta = self._meta()
        for db in (4, 8):
            want = (meta.enc.n_words * 4    # linearized index words
                    + 4                     # row index (int32)
                    + db)                   # value
            assert plan_mod.stream_elem_bytes(meta, db) == want

    def test_resident_exact_bytes(self):
        meta = self._meta()
        R, db = 8, 4
        i_max = max(meta.dims)
        want = (sum(meta.dims) * R * db     # all factors
                + i_max * R * db            # worst-mode output accumulator
                + i_max * R * db            # Φ's resident B operand
                + 4 + R * db)               # carry (row, value) pair
        assert plan_mod.streaming_resident_bytes(meta, R, db) == want

    def test_incore_working_set_exact_bytes(self):
        meta = self._meta()
        R, db = 8, 4
        want = (heuristics.stream_len(meta)
                * plan_mod.stream_elem_bytes(meta, db)
                + plan_mod.streaming_resident_bytes(meta, R, db))
        assert plan_mod.incore_working_set_bytes(meta, R, db) == want

    def test_chunk_hbm_exact_bytes(self):
        """Two in-flight chunks (compute + prefetch) plus the residency."""
        meta = self._meta()
        R, db = 8, 4
        for chunk_m in (64, 256, 1024):
            want = (2 * chunk_m * plan_mod.stream_elem_bytes(meta, db)
                    + plan_mod.streaming_resident_bytes(meta, R, db))
            assert plan_mod.chunk_hbm_bytes(meta, chunk_m, R, db) == want

    def test_needs_streaming_strict_boundary(self):
        """Streaming triggers STRICTLY above the budget: a working set
        exactly equal to device_bytes stays in-core."""
        meta = self._meta()
        ws = plan_mod.incore_working_set_bytes(meta, 8)
        assert not plan_mod.needs_streaming(meta, 8, ws)
        assert plan_mod.needs_streaming(meta, 8, ws - 1)
        assert plan_mod.make_plan(meta, 8, device_bytes=ws).streaming \
            is None
        assert plan_mod.make_plan(meta, 8,
                                  device_bytes=ws - 1).streaming \
            is not None

    def test_chosen_chunk_fits_budget_and_alignment(self):
        """Above the advisory floor the chosen chunk's double-buffered
        footprint fits the budget, sits on the alignment grid, and one
        more alignment step would overflow."""
        meta = self._meta()
        R, align = 8, 64
        resident = plan_mod.streaming_resident_bytes(meta, R)
        elem = plan_mod.stream_elem_bytes(meta)
        for chunks_worth in (2, 5, 11):
            budget = resident + 2 * elem * (chunks_worth * align) + 1
            cm = plan_mod.choose_chunk_m(meta, R, budget, align)
            assert cm == chunks_worth * align
            assert cm % align == 0
            assert plan_mod.chunk_hbm_bytes(meta, cm, R) <= budget
            assert plan_mod.chunk_hbm_bytes(meta, cm + align, R) > budget

    def test_chunk_advisory_floor_and_stream_cap(self):
        """Below the floor one aligned chunk is returned (advisory, like
        the VMEM choosers); a huge budget caps at the aligned stream."""
        meta = self._meta()
        align = 64
        assert plan_mod.choose_chunk_m(meta, 8, 0, align) == align
        padded = -(-heuristics.stream_len(meta) // align) * align
        assert plan_mod.choose_chunk_m(meta, 8, 1 << 50, align) == padded

    def test_chunk_count_block_m_independent(self):
        """n_chunks is a property of (stream, chunk_m), not of the block
        padding: the executor's grid over the block_m-padded stream
        matches the model for every block size dividing chunk_m."""
        from repro.core import stream as stream_mod
        x = synthetic.uniform_tensor((64, 48, 32), 2000, seed=0)
        at = alto.build(x, n_partitions=4)
        hs = stream_mod.host_stream(at, 0)
        for chunk_m in (64, 128, 512):
            want = plan_mod.chunk_count(at.meta, chunk_m)
            for bm in (8, 16, 32, 64):
                padded = hs.padded_len(bm)
                executed = -(-padded // chunk_m)
                assert executed == want, (chunk_m, bm)

    def test_stream_plan_records_model_outputs(self):
        """The StreamPlan on a streaming plan carries exactly the model
        numbers: chunk from choose_chunk_m at the plan's alignment,
        count from chunk_count, working set from the in-core model."""
        meta = self._meta()
        R = 8
        budget = plan_mod.streaming_resident_bytes(meta, R) + 4096
        plan = plan_mod.make_plan(meta, R, device_bytes=budget)
        sp = plan.streaming
        assert sp is not None
        align = max(m.block_m for m in plan.modes)
        assert sp.chunk_m == plan_mod.choose_chunk_m(meta, R, budget,
                                                     align)
        assert sp.n_chunks == plan_mod.chunk_count(meta, sp.chunk_m)
        assert sp.device_bytes == budget
        assert sp.stream_bytes == plan_mod.incore_working_set_bytes(
            meta, R)
