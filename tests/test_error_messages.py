"""Error-hygiene regression tests: every ValueError core/ raises must NAME
the offending shape/knob/key (mpwlint rule R3 enforces the shape of the
message; these tests pin each message's content), plus the typed errors the
bare-assert promotions introduced.
"""
from __future__ import annotations

import numpy as np
import pytest

from repro.configs.base import CommConfig
from repro.core import ring
from repro.core.path import INTERPOD, WidePath


def _path(**comm_kw) -> WidePath:
    return WidePath(axis="pod", comm=CommConfig(**comm_kw))


# -- collectives --------------------------------------------------------------

def test_streamed_psum_unknown_algo_names_algo():
    from repro.core.collectives import streamed_psum
    with pytest.raises(ValueError, match=r"unknown comm algo 'bogus'"):
        streamed_psum({"g": np.zeros(4, np.float32)}, _path(algo="bogus"))


def test_site_allreduce_unequal_sites_names_sizes():
    from repro.core.collectives import site_allreduce
    with pytest.raises(ValueError, match=r"equal pods per site.*\[1, 2\]"):
        site_allreduce({"g": np.zeros(4, np.float32)}, _path(),
                       site_groups=[[0], [1, 2]])


def test_wide_allreduce_unknown_mode_names_mode():
    from repro.core.collectives import wide_allreduce
    with pytest.raises(ValueError, match=r"unknown comm mode 'bogus'"):
        wide_allreduce({"g": np.zeros(4, np.float32)}, _path(mode="bogus"))


# -- buckets / streams --------------------------------------------------------

def test_plan_buckets_layer_mismatch_names_dims():
    from repro.core.buckets import plan_buckets
    leaves = [np.zeros((2, 3), np.float32), np.zeros((3, 3), np.float32)]
    with pytest.raises(ValueError,
                       match=r"disagree on the layers dim: \[2, 3\]"):
        plan_buckets(leaves, [True, True], 64)


# -- ring ---------------------------------------------------------------------

def test_ring_reduce_scatter_divisibility_names_extent(monkeypatch):
    # _ring_setup needs a live mesh axis; stub it so the shape check —
    # which precedes any collective — is reachable host-side.
    monkeypatch.setattr(ring, "_ring_setup",
                        lambda axis, sub: (3, 0, (0, 1, 2)))
    with pytest.raises(ValueError,
                       match=r"dim 0 extent 4 not divisible by world 3"):
        ring.ring_reduce_scatter(np.zeros((4, 2), np.float32), 0, "pod")


# -- MPW facade ---------------------------------------------------------------

def test_mpw_variadic_alignment_names_both_lengths():
    from repro.core.api import MPW
    m = MPW.Init()
    try:
        with pytest.raises(ValueError,
                           match=r"2 entries but links has 1"):
            m.CreatePathVariadic(streams_per_hop=(4, 4), links=[INTERPOD])
    finally:
        m.Finalize()


def test_mpw_set_algorithm_unknown_names_algo():
    from repro.core.api import MPW
    m = MPW.Init()
    try:
        pid = m.CreatePath()
        with pytest.raises(ValueError, match=r"unknown algo 'bogus'"):
            m.setAlgorithm(pid, "bogus")
    finally:
        m.Finalize()


def test_mpw_set_bucket_size_names_value():
    from repro.core.api import MPW
    m = MPW.Init()
    try:
        pid = m.CreatePath()
        with pytest.raises(ValueError, match=r"bucket size.*got -1"):
            m.setBucketSize(pid, -1)
    finally:
        m.Finalize()


def test_mpw_observe_hop_out_of_range_names_hop():
    from repro.core.api import MPW
    m = MPW.Init()
    try:
        pid = m.CreatePath()
        with pytest.raises(ValueError, match=r"hop 5 out of range"):
            m.Observe(pid, 0.1, hop=5)
    finally:
        m.Finalize()


# -- file transfer ------------------------------------------------------------

def test_file_transfer_unknown_codec_names_codec():
    from repro.core.filetransfer import FileTransfer
    with pytest.raises(ValueError, match=r"unknown file codec 'bogus'"):
        FileTransfer(_path(), compress="bogus")


# -- topology -----------------------------------------------------------------

def test_degrade_factor_names_factor():
    from repro.core.topology import LinkProfile
    prof = LinkProfile("l", 1e-3, 1e9)
    with pytest.raises(ValueError, match=r"\(0, 1\], got 1.5"):
        prof.degrade(1.5, (0, 5))


def test_unknown_fault_kind_names_kind():
    from repro.core.topology import Fault, LinkProfile
    prof = LinkProfile("l", 1e-3, 1e9).with_fault(Fault("bogus"))
    with pytest.raises(ValueError, match=r"unknown fault kind 'bogus'"):
        prof.health(0)


def test_duplicate_site_names_site():
    from repro.core.topology import Topology
    t = Topology()
    t.add_site("ams")
    with pytest.raises(ValueError, match=r"duplicate site 'ams'"):
        t.add_site("ams")


def test_pods_already_assigned_names_pods():
    from repro.core.topology import Topology
    t = Topology()
    t.add_site("a", pods=(0,))
    with pytest.raises(ValueError, match=r"pods \{0\} already assigned"):
        t.add_site("b", pods=(0,))


def test_pod_groups_gap_names_covered():
    from repro.core.topology import Topology
    t = Topology()
    t.add_site("a", pods=(1,))
    with pytest.raises(ValueError, match=r"must tile the pod axis.*\[1\]"):
        t.pod_groups()


def test_route_unknown_metric_names_metric():
    from repro.core.topology import cosmogrid_topology
    with pytest.raises(ValueError, match=r"unknown metric 'bogus'"):
        cosmogrid_topology().route("amsterdam", "tokyo", "bogus")


def test_route_coincident_endpoints_names_site():
    from repro.core.topology import cosmogrid_topology
    with pytest.raises(ValueError, match=r"tokyo -> tokyo.*coincide"):
        cosmogrid_topology().route("tokyo", "tokyo")


# -- chaos --------------------------------------------------------------------

def test_incident_log_unknown_kind_names_kind():
    from repro.core.chaos import IncidentLog
    with pytest.raises(ValueError, match=r"unknown incident kind 'bogus'"):
        IncidentLog().add(0, "bogus", "x")


# -- promoted bare asserts (R3 satellite) ------------------------------------

def test_quant_int8_ref_block_mismatch_names_shapes():
    from repro.kernels.ref import quant_int8_ref
    import jax.numpy as jnp
    with pytest.raises(ValueError, match=r"last dim 10.*block 256"):
        quant_int8_ref(jnp.zeros((4, 10)))


def test_quant_int8_2d_block_mismatch_names_shapes():
    # the kernel path refuses a ragged trailing dim; it never falls back
    from repro.kernels.ops import quant_int8
    import jax.numpy as jnp
    with pytest.raises(ValueError, match=r"trailing dim 10.*block=256"):
        quant_int8(jnp.zeros((4, 10)), impl="pallas_interpret")


def test_flash_attention_gqa_mismatch_names_heads():
    from repro.kernels.ops import flash_attention
    import jax.numpy as jnp
    q = jnp.zeros((1, 4, 3, 8))
    kv = jnp.zeros((1, 4, 2, 8))
    with pytest.raises(ValueError, match=r"q heads 3.*kv heads 2"):
        flash_attention(q, kv, kv)


def test_flash_kernel_group_mismatch_names_heads():
    from repro.kernels.flash_attention import flash_attention_bhsd
    import jax.numpy as jnp
    q = jnp.zeros((3, 4, 8))
    kv = jnp.zeros((2, 4, 8))
    with pytest.raises(ValueError, match=r"q heads 3 != kv heads 2"):
        flash_attention_bhsd(q, kv, kv, group=2)


def test_pd_rank_mismatch_names_shape_and_axes():
    from repro.models.param import PD
    with pytest.raises(ValueError, match=r"shape \(2, 3\) and axes"):
        PD(shape=(2, 3), axes=("d",))


def test_trainer_run_without_state_raises_runtime_error():
    from repro.runtime.train_loop import Trainer
    t = Trainer.__new__(Trainer)
    t.state = None
    with pytest.raises(RuntimeError, match=r"init_or_restore"):
        t.run(iter([]), 1)
