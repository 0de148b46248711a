"""Crash-surviving in-flight requests: the chaos soak (SERVING.md rung 22).

The durability contract under test: with boundary checkpoints on, a
pool that poisons mid-decode — mid-window, mid-harvest, mid-swap,
mid-pipeline-harvest — revives with every journaled in-flight request
restored into a fresh slot and completes it BIT-IDENTICAL to an
uninterrupted run, while the global invariants hold at every settle
point: page conservation, no stuck tickets, monotone emitted offsets,
typed failures only.

Two legs share one harness (``testing/chaos.py``):

* a short deterministic subset — pinned server shapes, seeds chosen to
  exercise revive-with-restore at one-step windows, at longer windows,
  on a recurrent block and on a window block — fast enough for tier-1;
* the seeded soak — ``@slow``, 24 campaigns whose whole decision
  stream (server shape, prompts, consumer mix, fault plans) derives
  from the campaign seed.

Plus the ``serving_debug_pages`` audit's loud-failure contract: a
seeded page leak (a FaultyCache subclass stealing a page at the admit
seam) must poison the pool with the typed, non-retryable
``PageAccountingError`` at the next quiescent boundary.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kvedge_tpu.models import TransformerConfig, generate, init_params
from kvedge_tpu.models.serving import PagedGenerationServer
from kvedge_tpu.runtime.failures import (
    PageAccountingError,
    ServingFailure,
)
from kvedge_tpu.testing.chaos import run_chaos_campaign
from kvedge_tpu.testing.servingfaults import FaultyCache

pytestmark = pytest.mark.chaos

CFG = TransformerConfig(
    vocab=128, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2, d_ff=64,
    max_seq=64,
)

# Pinned server shapes for the deterministic tier-1 subset: one per
# shape of decode trip the durability machinery hooks into. With each,
# the decode-loop seam indices (prefill seams not counted) at which a
# raise is SURE to poison with a journaled request still in flight,
# whatever the interleaving: the first boundary checkpoints
# (checkpoint_every=1) and a request of n_new=6 outlives the seams up
# to 3.
ONESTEP = dict(checkpoint_every=1, window=1)
OVERLAP = dict(checkpoint_every=1, window=2)
SURE = (1, 3)

ROUNDS = 2
PER_ROUND = 3

_ORACLE_MEMO: dict = {}


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def oracle(params):
    """Fault-free greedy reference, memoized across campaigns (the
    prompts are seed-drawn, so collisions across campaigns are real
    compile savings, not luck)."""

    def fn(prompt, n_new):
        key = (tuple(prompt), n_new)
        if key not in _ORACLE_MEMO:
            out = generate(params, jnp.asarray([prompt], jnp.int32),
                           CFG, n_new=n_new)
            _ORACLE_MEMO[key] = [int(t) for t in np.asarray(out)[0]]
        return _ORACLE_MEMO[key]

    return fn


# ---- deterministic subset (tier-1): revive-with-restore per shape --------


def _loop_seams_only(round_i, server, cache, plan):
    """The plan counts only the decode loop's seams: a fault on a
    submitter's prefill fails that one request and poisons nothing,
    and where the prefills fall among the loop's seams is thread
    interleaving. Every seam that is left poisons the pool."""
    count = plan.at_seam
    plan.at_seam = lambda label: (
        None if label.startswith("prefill") else count(label))


def _assert_armed(res, sure):
    """The campaign's decisions (a pure function of its seed) armed a
    raise at a seam the loop is sure to reach. A seed that stops doing
    so — the decision stream shifted — is re-pinned, not retried."""
    lo, hi = sure
    plans = [re.search(r"kind=(\w+) fire_at=(\d+)", ln)
             for ln in res.trace if ln.startswith("[plan]")]
    assert any(m[1] == "raise" and lo <= int(m[2]) <= hi
               for m in plans), (
        f"seed {res.seed} arms no raise at loop seams {lo}..{hi}: "
        f"re-pin it ({[m[0] for m in plans]})")


def _block_oracle(cfg, params):
    """A patterned block's fault-free reference (``decode.generate``
    refuses a pattern): a server of the same block that nothing
    wounds, memoized like the plain one."""
    memo: dict = {}

    def fn(prompt, n_new):
        key = (tuple(prompt), n_new)
        if key not in memo:
            server = PagedGenerationServer(params, cfg, slots=1, pages=24,
                                           page_size=4, prefix_cache=False)
            try:
                memo[key] = server.submit(list(prompt), n_new)
            finally:
                server.close()
        return memo[key]

    return fn


@pytest.mark.parametrize(
    "seed,config,sure,block",
    [(17, ONESTEP, SURE, ""), (19, ONESTEP, SURE, ""),
     (2, OVERLAP, SURE, ""), (5, OVERLAP, SURE, "recurrent"),
     (9, OVERLAP, SURE, "window-block")],
    ids=["w1-17", "w1-19", "overlap-2", "recurrent-5", "window-block-9"],
)
def test_deterministic_campaign(params, oracle, probe_blocks, seed, config,
                                sure, block):
    """Seeds pinned to poison at least once per campaign: the run must
    revive, restore journaled requests, and finish every survivor
    bit-identical (the harness raises InvariantViolation otherwise).
    On a patterned block (``probe_blocks``) what the journal brings
    back holds a row's recurrent state, or its second pool's pages."""
    cfg = CFG
    if block:
        cfg, params = probe_blocks[block]
        oracle = _block_oracle(cfg, params)
    res = run_chaos_campaign(
        params, cfg, seed=seed, rounds=ROUNDS,
        requests_per_round=PER_ROUND, n_new=6, config=config,
        oracle=oracle, wound=_loop_seams_only,
    )
    assert res.completed + res.failed == ROUNDS * PER_ROUND
    _assert_armed(res, sure)
    # These seeds are chosen BECAUSE they poison mid-flight with
    # journaled work to bring back — a campaign that stops exercising
    # the restore path is a regression even if nothing else breaks.
    assert res.revives >= 1, res.fired
    assert res.restored_total >= 1, res.fired
    # Restored requests complete: failures are only ever the typed
    # pre-admission kind, never the whole round.
    assert res.completed >= res.restored_total


def test_campaign_decisions_replay_from_seed(params, oracle):
    """Same seed, same decisions: server shape, prompts, and fault
    plans replay exactly (the trace records them). Seam ARRIVAL order
    still depends on thread interleaving — that is what the trace is
    for — so the replay contract is the decision stream, not the
    firing seam."""
    a = run_chaos_campaign(params, CFG, seed=9, rounds=ROUNDS,
                           requests_per_round=PER_ROUND, n_new=6,
                           config=ONESTEP, oracle=oracle)
    b = run_chaos_campaign(params, CFG, seed=9, rounds=ROUNDS,
                           requests_per_round=PER_ROUND, n_new=6,
                           config=ONESTEP, oracle=oracle)
    assert a.config == b.config
    # Decision lines (plans, submissions) are positionally identical;
    # runtime lines (revives, outcomes) may interleave differently.
    decisions = [ln for ln in a.trace
                 if ln.startswith(("[campaign]", "[plan]"))
                 or "submit" in ln]
    assert decisions == [ln for ln in b.trace
                         if ln.startswith(("[campaign]", "[plan]"))
                         or "submit" in ln]
    assert a.completed + a.failed == b.completed + b.failed


# ---- shared-prefix mix: refcount-aware conservation (rung 24) ------------


@pytest.mark.prefix
@pytest.mark.parametrize(
    "seed,config",
    [(13, ONESTEP), (15, ONESTEP), (32, OVERLAP)],
    ids=["w1-13", "w1-15", "overlap-32"],
)
def test_prefix_mix_campaign(params, oracle, seed, config):
    """Chaos with the prefix cache ON and prompts sharing page-sized
    stems: faults land on COW admissions, leased pages, and
    journal-refcount checkpoints. The settle check runs the
    refcount-aware conservation invariant — shared pages counted once,
    per-page refcounts equal to the holding-entry count, shadow store
    empty, force-evict returning the pool to every-page-free — and
    every completion still matches the fault-free oracle."""
    res = run_chaos_campaign(
        params, CFG, seed=seed, rounds=ROUNDS,
        requests_per_round=PER_ROUND, n_new=6, config=config,
        oracle=oracle, prefix_mix=True, wound=_loop_seams_only,
    )
    assert res.completed + res.failed == ROUNDS * PER_ROUND
    _assert_armed(res, (0, SURE[1]))
    assert res.revives >= 1, res.fired


@pytest.mark.prefix
@pytest.mark.slow
@pytest.mark.parametrize("seed", range(200, 212))
def test_prefix_mix_soak(params, oracle, seed):
    res = run_chaos_campaign(
        params, CFG, seed=seed, rounds=ROUNDS,
        requests_per_round=PER_ROUND, n_new=6, oracle=oracle,
        prefix_mix=True,
    )
    assert res.completed + res.failed == ROUNDS * PER_ROUND


# ---- the seeded soak (slow): drawn shapes, >= 20 campaigns ---------------


@pytest.mark.slow
@pytest.mark.parametrize("seed", range(100, 124))
def test_soak_campaign(params, oracle, seed):
    """Randomized multi-fault campaigns: server shape, prompts,
    consumer mix, and per-round fault plans all drawn from the seed.
    Zero invariant violations over the fleet is the acceptance bar."""
    res = run_chaos_campaign(
        params, CFG, seed=seed, rounds=ROUNDS,
        requests_per_round=PER_ROUND, n_new=6, oracle=oracle,
    )
    assert res.completed + res.failed == ROUNDS * PER_ROUND


# ---- serving_debug_pages: a seeded leak fails loud and typed -------------


class _LeakyCache(FaultyCache):
    """Steals one free page at the first admit — the books then claim
    one fewer page than the pool owns, exactly the class of host-side
    bug the boundary audit exists to catch."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.leaked = False

    def admit(self, *args, **kwargs):
        out = super().admit(*args, **kwargs)
        if not self.leaked and self._free:
            self._free.pop()
            self.leaked = True
        return out


def test_debug_pages_audit_trips_on_seeded_leak(params):
    cache = _LeakyCache(CFG, slots=2, pages=16, page_size=4)
    server = PagedGenerationServer(params, CFG, cache=cache,
                                   debug_pages=True, prefix_cache=False)
    try:
        with pytest.raises(ServingFailure):
            server.submit([3, 1, 4], n_new=6)
        # The poison is the TYPED audit failure, and it is terminal:
        # a replacement process running the same code leaks the same
        # way, so retrying against it would be a lie.
        assert isinstance(server._poison, PageAccountingError)
        assert server._poison.retryable is False
        assert "free" in str(server._poison)
    finally:
        server.close()


def test_debug_pages_audit_passes_clean_pool(params):
    """The audit is a no-op on a healthy pool — whole requests run
    under it without tripping, and the books balance at close."""
    cache = FaultyCache(CFG, slots=2, pages=16, page_size=4)
    server = PagedGenerationServer(params, CFG, cache=cache,
                                   debug_pages=True, checkpoint_every=1,
                                   prefix_cache=False)
    try:
        out = server.submit([3, 1, 4], n_new=6)
        want = generate(params, jnp.asarray([[3, 1, 4]], jnp.int32),
                        CFG, n_new=6)
        assert out == [int(t) for t in np.asarray(want)[0]]
        assert server.degraded is None
        acct = cache.page_accounting()
        assert acct["free"] == acct["pages_total"]
    finally:
        server.close()
