"""Conformance tests for the ``serving/backend.py::Backend`` protocol.

The tier-hop contract used to exist only by convention across three
backends; this file holds every implementation (the duck-typed
``CacheBackend`` base, ``PagedBackend``, ``_JaxBackend``,
``_SimBackend``) to the explicit
Protocol, and exercises the base implementation's tier moves live so the
generic ``demote_copy``/``promote_copy``/``free_tier`` dispatch stays
wired to the named hops.
"""
import pytest

jax = pytest.importorskip("jax")

from repro.core.knowledge_tree import CacheBackend, Node  # noqa: E402
from repro.core.profiler import A10G_MISTRAL_7B  # noqa: E402
from repro.serving.backend import Backend, conforms  # noqa: E402
from repro.serving.engine import _JaxBackend  # noqa: E402
from repro.serving.runtime import PagedBackend  # noqa: E402
from repro.serving.simulator import _SimBackend  # noqa: E402


def _node():
    return Node(doc_id=0, parent=None, n_tokens=4, bytes_=64)


@pytest.mark.parametrize("make", [
    CacheBackend,
    lambda: PagedBackend(store=None, disk=None),
    _JaxBackend,
    lambda: _SimBackend(A10G_MISTRAL_7B),
], ids=["base", "paged", "jax", "sim"])
def test_backend_conforms(make):
    """Every implementation satisfies the Protocol (method presence)."""
    assert conforms(make())


def test_protocol_is_runtime_checkable_and_strict():
    """A lookalike missing one hop method must NOT conform — the protocol
    exists exactly to catch this drift (e.g. a misspelled free method)."""

    class Almost:
        def swap_out(self, node): return 0.0
        def load(self, node): return 0.0
        def spill(self, node): return 0.0
        def fetch(self, node): return 0.0
        def free_gpu(self, node): pass
        def free_host(self, node): pass
        # free_disk missing
        def demote_copy(self, node, level): return 0.0
        def promote_copy(self, node, level): return 0.0
        def free_tier(self, node, level): pass

    assert not conforms(Almost())
    assert not isinstance(object(), Backend)


def test_base_backend_hops_return_seconds_and_move_payloads():
    """Live exercise of the contract's semantics on the accounting base:
    hops return float seconds, frees return None, and the tier-indexed
    dispatch reaches the same payload slots as the named hops."""
    b, n = CacheBackend(), _node()
    n.payload_gpu = "seg"
    assert isinstance(b.demote_copy(n, 0), float)    # swap_out
    assert n.payload_host == "seg"
    assert isinstance(b.demote_copy(n, 1), float)    # spill
    assert n.payload_disk == "seg"
    assert b.free_tier(n, 0) is None and n.payload_gpu is None
    assert isinstance(b.promote_copy(n, 2), float)   # fetch
    assert isinstance(b.promote_copy(n, 1), float)   # load
    assert n.payload_gpu == "seg"
    b.free_tier(n, 2)
    assert n.payload_disk is None


def test_sim_backend_hop_costs_are_analytic_transfer_times():
    """The simulator backend's seconds come from the hardware profile, so
    they must scale with payload bytes (and with_tp scales the link)."""
    prof = A10G_MISTRAL_7B
    b = _SimBackend(prof)
    small, big = _node(), _node()
    small.bytes_, big.bytes_ = 2**20, 2**24
    small.payload_gpu = big.payload_gpu = object()
    assert b.swap_out(big) > b.swap_out(small) > 0.0
    b2 = _SimBackend(prof.with_tp(2))
    assert b2.swap_out(big) < b.swap_out(big)   # tp-parallel shard copies
