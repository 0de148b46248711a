"""Seeded multi-fault chaos campaigns for the durable serving stack.

``testing/servingfaults.py`` proves ONE fault terminates cleanly; this
module is the soak that rung 22 (SERVING.md — boundary checkpoints +
resume-after-revive) is accepted against: a campaign drives several
ROUNDS of seeded traffic into one long-lived server wearing a
:class:`~kvedge_tpu.testing.servingfaults.FaultyCache`, arms a fresh
seeded :class:`~kvedge_tpu.testing.servingfaults.FaultPlan` each round
(so faults land mid-window, mid-harvest, mid-swap, mid-prefill —
wherever the seam counter happens to fall), heals every poison with
``revive()``, and checks the GLOBAL invariants after every round:

1. **Page conservation** — the pool's books balance
   (``kvcache.page_accounting``: ``free + live == pages_total``, no
   negative refcount, no page both free and live) and every page is
   free once the round's requests settle. The server's own
   ``debug_pages`` audit runs at every quiescent boundary during the
   round, so a transient leak poisons loudly instead of hiding.
   With the shared-prefix mix (``prefix_mix=True``) the check is
   REFCOUNT-AWARE: registry-pinned pages are legitimately live after
   settle, so conservation becomes ``free + |distinct pinned pages|
   == pages_total`` (a page shared by several entries counts ONCE),
   every pinned page's refcount must equal exactly the number of
   entries holding it (no leaked retains after poison/revive or a
   journal-refcount restore), the journal's shadow store must be
   empty, and force-evicting the whole registry must return the pool
   to every-page-free.
2. **No stuck tickets** — every submission terminates (tokens or a
   typed error) within the round's deadline; the journal and the
   active set are empty once the round settles.
3. **Monotone emitted offsets** — a streamed consumer's token log only
   grows, and never beyond its ``n_new`` budget (no duplicate delivery
   after a resume, no over-emission).
4. **Bit-identity vs the fault-free oracle** — every request that
   completes matches the tokens an uninterrupted greedy run produces;
   with boundary checkpoints on, requests that were in flight when the
   pool poisoned complete (restored from the journal) rather than
   failing. Failures that do occur (e.g. a fault raising into the
   submit path before admission) must be typed.

Seed-derived, same replay contract as the fault harnesses: the
campaign's whole DECISION stream — server shape, prompts, consumer
mix, per-round fault plans — derives from ``random.Random(seed)`` and
is appended to ``trace``. The seam a plan ends up firing on still
depends on thread interleaving (submission arrival order is real
concurrency), which is exactly why the trace records it: a failing
campaign ships both the decisions and what they landed on.
"""

from __future__ import annotations

import dataclasses
import random
import threading
import time

from kvedge_tpu.runtime.failures import (
    PageAccountingError,
    ServingFailure,
)
from kvedge_tpu.testing.faults import InvariantViolation
from kvedge_tpu.testing.servingfaults import (
    FaultPlan,
    FaultyCache,
    InjectedFault,
)

__all__ = ["ChaosResult", "run_chaos_campaign"]


@dataclasses.dataclass
class ChaosResult:
    """One campaign's outcome (all invariants already enforced)."""

    seed: int
    config: dict
    rounds: int
    fired: list  # seam label (or None) per round
    completed: int
    failed: int
    revives: int
    restored_total: int
    trace: list


@dataclasses.dataclass
class _Sub:
    prompt: list
    n_new: int
    streaming: bool
    want: list
    tokens: list | None = None
    got: list = dataclasses.field(default_factory=list)
    over_emitted: bool = False
    error: Exception | None = None
    finished: threading.Event = dataclasses.field(
        default_factory=threading.Event
    )


def _draw_config(rng: random.Random) -> dict:
    """The campaign's server shape: checkpoints always ON (this is the
    durability soak), the rest drawn so the seeded fleet covers
    one-step and longer windows."""
    # The two draws the retired speculation knobs took are still taken:
    # a seed's prompts and fault plans stay what they were chosen for.
    spec = rng.choice([0, 0, 2])
    shape = {
        "checkpoint_every": rng.choice([1, 2]),
        "window": rng.choice([1, 2, 4]),
    }
    if spec:
        rng.choice([0, 2])
    return shape


def run_chaos_campaign(params, tcfg, seed: int, *, rounds: int = 2,
                       requests_per_round: int = 3, n_new: int = 8,
                       slots: int = 3, pages: int = 24,
                       page_size: int = 4, vocab: int | None = None,
                       prompt_len: tuple = (3, 7),
                       config: dict | None = None, oracle=None,
                       wound=None, prefix_mix: bool = False,
                       join_timeout_s: float = 180.0) -> ChaosResult:
    """Run one seeded campaign against a fresh server; raise
    :class:`~kvedge_tpu.testing.faults.InvariantViolation` (carrying
    the full decision trace) on any breach, else return the result.

    ``config`` pins the server shape instead of drawing it (the
    deterministic tier-1 subset pins a cheap shape; the soak draws).
    ``oracle(prompt, n_new) -> tokens`` supplies the fault-free
    reference (tests memoize it across campaigns); None builds one
    from ``models.generate`` per prompt. ``wound(round_i, server,
    cache, plan)`` runs after each round's plan is armed — the hook
    slice/capacity tests use to compose extra damage (follower loss,
    bucket pressure) on top of the seam fault. ``prefix_mix`` turns
    the prefix cache ON and draws prompts from a small set of shared
    page-sized stems, so faults land on COW admissions, leased pages,
    and journal-refcount checkpoints — the settle check then runs the
    refcount-aware conservation invariant (docstring point 1).
    """
    from kvedge_tpu.models.serving import (
        PagedGenerationServer,
        RequestCancelled,
        ServerBusy,
        ServerClosed,
    )

    rng = random.Random(seed)
    cfg_draw = dict(_draw_config(rng))
    if config:
        cfg_draw.update(config)
    trace = [f"[campaign] seed={seed} config={cfg_draw}"]
    allowed = (ServingFailure, ServerBusy, ServerClosed,
               RequestCancelled, InjectedFault)

    if oracle is None:
        import jax.numpy as jnp
        import numpy as np

        from kvedge_tpu.models import generate

        def oracle(prompt, n):
            out = generate(params, jnp.asarray([prompt], jnp.int32),
                           tcfg, n_new=n)
            return [int(t) for t in np.asarray(out)[0]]

    vocab = vocab or tcfg.vocab
    cache = FaultyCache(tcfg, slots=slots, pages=pages,
                        page_size=page_size)
    # Default mix runs prefix_cache off: pinned prefix pages are
    # LEGITIMATELY live across requests, which would poison the plain
    # every-page-free check — and prefix reuse is orthogonal to the
    # basic durability story. ``prefix_mix`` flips it on and switches
    # the settle check to the refcount-aware invariant.
    #
    # The observability stack (rung 25) runs ON in every campaign: the
    # SLO engine snapshots and occupancy ring sample at the same
    # boundaries faults land on, and the flight-recorder completeness
    # invariant below asserts the bundle survives every poison/revive.
    from kvedge_tpu.runtime.slo import SloObjectives

    server = PagedGenerationServer(
        params, tcfg, cache=cache, prefix_cache=prefix_mix,
        debug_pages=True, slo=SloObjectives(), occupancy_ring=64,
        **cfg_draw,
    )
    stems = []
    if prefix_mix:
        # Two fixed page-multiple stems (so full-block trie hits) the
        # seeded prompts below share; suffixes diverge mid-page too,
        # exercising the COW path.
        stems = [
            [rng.randrange(1, vocab) for _ in range(page_size)],
            [rng.randrange(1, vocab) for _ in range(2 * page_size)],
        ]

    def fail(msg):
        raise InvariantViolation(f"[chaos seed={seed}] {msg}", trace)

    fired, completed, failed = [], 0, 0
    revives = restored_total = 0
    try:
        for round_i in range(rounds):
            plan = FaultPlan(
                seed=rng.randrange(1 << 30),
                # No "hang": the single-host pool has no deadline
                # watchdog, so a parked seam would stall the round,
                # not poison it — raise/delay cover the poison and
                # slow-path stories the soak is after.
                kinds=("raise", "delay"),
                # Coalesced boundary checkpoints (one swapout per
                # boundary, unchanged requests skipped) mean a round
                # crosses far fewer device seams than the per-request
                # swapout era — indices past ~10 are reached only on
                # lucky interleavings. Keep the drawn fire index low
                # so every plan lands mid-flight deterministically.
                fire_window=(1, rng.randrange(3, 10)),
                delay_s=0.05,
            )
            cache.plan = plan
            trace.extend(plan.trace[:1])
            if wound is not None:
                wound(round_i, server, cache, plan)
            subs = []
            for _ in range(requests_per_round):
                prompt = [rng.randrange(1, vocab)
                          for _ in range(rng.randrange(*prompt_len))]
                if prefix_mix and rng.random() < 0.75:
                    prompt = rng.choice(stems) + prompt
                subs.append(_Sub(
                    prompt=prompt, n_new=n_new,
                    streaming=rng.random() < 0.5,
                    want=oracle(prompt, n_new),
                ))
            threads = [
                threading.Thread(target=_drive, args=(server, sub),
                                 name=f"chaos-{round_i}-{i}",
                                 daemon=True)
                for i, sub in enumerate(subs)
            ]
            for i, sub in enumerate(subs):
                trace.append(
                    f"[round {round_i} submit {i}] "
                    f"len={len(sub.prompt)} "
                    f"{'stream' if sub.streaming else 'block'}"
                )
                threads[i].start()

            def heal(round_i=round_i):
                """Revive a poisoned pool; returns True if it healed
                one. Page-audit poisons are invariant breaches, never
                healed — they mean the books are already broken."""
                nonlocal revives, restored_total
                if server.degraded is None:
                    return False
                poison = server._poison
                if isinstance(poison, PageAccountingError):
                    fail(f"round {round_i}: page books broken — "
                         f"{poison}")
                server._thread.join(timeout=60)
                if server._thread.is_alive():
                    fail(f"round {round_i}: decode thread still "
                         "alive after poison")
                restored = server.revive()
                revives += 1
                restored_total += restored
                trace.append(f"[round {round_i}] revived, "
                             f"restored={restored}")
                return True

            # Pump the round: heal every poison until all settle.
            deadline = time.monotonic() + join_timeout_s
            while not all(s.finished.is_set() for s in subs):
                if time.monotonic() > deadline:
                    plan.close()
                    fail(f"round {round_i}: stuck ticket — a request "
                         f"never terminated within {join_timeout_s:g}s")
                if not heal():
                    time.sleep(0.01)
            for t in threads:
                t.join(timeout=10)
            # A poison that failed every request before the pump saw it
            # (e.g. the very first checkpoint's swapout raising, with
            # nothing journaled yet) still needs healing — the settle
            # checks below run against a live pool, and the next round
            # submits into it.
            heal()
            fired.append(plan.fired_on)
            trace.append(f"[round {round_i}] fired_on={plan.fired_on}")

            # Invariant 3/4 per request; 1/2 for the settled pool.
            for i, sub in enumerate(subs):
                if sub.over_emitted:
                    fail(f"round {round_i} request {i}: stream emitted "
                         f"beyond its n_new={n_new} budget")
                if sub.error is not None:
                    if not isinstance(sub.error, allowed):
                        fail(f"round {round_i} request {i} died "
                             f"UNTYPED: {type(sub.error).__name__}: "
                             f"{sub.error}")
                    failed += 1
                    trace.append(f"[round {round_i} outcome {i}] "
                                 f"{type(sub.error).__name__}")
                    continue
                if sub.tokens != sub.want:
                    fail(f"round {round_i} request {i}: tokens diverge "
                         f"from the fault-free oracle\n got "
                         f"{sub.tokens}\nwant {sub.want}")
                completed += 1
                trace.append(f"[round {round_i} outcome {i}] ok")
            _check_settled(server, cache, fail,
                           context=f"round {round_i}")
            _check_bundle(server, cache, fail,
                          context=f"round {round_i}",
                          timeline=completed > 0)
            plan.close()
        return ChaosResult(
            seed=seed, config=cfg_draw, rounds=rounds, fired=fired,
            completed=completed, failed=failed, revives=revives,
            restored_total=restored_total, trace=trace,
        )
    finally:
        cache.plan = None
        server.close()


def _drive(server, sub: _Sub) -> None:
    """One consumer. Streaming consumers keep the per-token log the
    monotone-offset invariant checks; both park across revive (no
    timeout — the campaign's pump owns the deadline)."""
    try:
        if sub.streaming:
            handle = server.submit_stream(sub.prompt, sub.n_new)
            for tok in handle:
                sub.got.append(tok)
                if len(sub.got) > sub.n_new:
                    sub.over_emitted = True
                    break
            sub.tokens = sub.prompt + sub.got
        else:
            sub.tokens = server.submit(sub.prompt, sub.n_new)
    except Exception as e:
        sub.error = e
    finally:
        sub.finished.set()


def _check_settled(server, cache, fail, *, context: str) -> None:
    """Invariants 1 + 2 once a round's requests have all terminated:
    balanced books, no journal residue, nothing still admitted. With
    the prefix cache off, every page must be free; with it on, the
    REFCOUNT-AWARE form applies — registry pins are the only
    legitimate holds, each counted once however many entries share
    it, each page's refcount exactly the holding-entry count, the
    journal's shadow store empty, and a full force-evict returns the
    pool to every-page-free (no leaked retains or leases)."""
    acct = cache.page_accounting()
    ok = (acct["free"] + acct["live"] == acct["pages_total"]
          and not acct["free_dup"] and not acct["neg_refs"]
          and not acct["free_live"])
    if not ok:
        fail(f"{context}: page books broken after settle: {acct}")
    with server._lock:
        holds: dict = {}
        for entry in server._prefix_entry_nodes.values():
            for p in entry["pages"]:
                holds[p] = holds.get(p, 0) + 1
        leases = dict(server._lease)
        shadow_nodes = len(server._prefix_shadow)
    if leases:
        fail(f"{context}: leases leaked after settle: {leases}")
    if acct["free"] + len(holds) != acct["pages_total"]:
        fail(f"{context}: pages leaked after settle "
             f"(free={acct['free']} pinned={len(holds)} "
             f"total={acct['pages_total']})")
    for p in range(acct["pages_total"]):
        want = holds.get(p, 0)
        got = cache.page_refcount(p)
        if got != want:
            fail(f"{context}: page {p} refcount {got} != "
                 f"{want} holding entries — leaked retain")
    stats = server.stats()
    if stats.get("journal_entries"):
        fail(f"{context}: journal residue after settle: "
             f"{stats['journal_entries']} entries")
    if shadow_nodes or stats.get("journal_shadow_bytes"):
        fail(f"{context}: shadow residue after settle: "
             f"{shadow_nodes} nodes, "
             f"{stats.get('journal_shadow_bytes')} bytes")
    if stats.get("in_flight"):
        fail(f"{context}: {stats['in_flight']} requests still "
             "admitted after settle")
    # The pins themselves must release cleanly: force-evict the whole
    # registry (and the host tier) and require every page free.
    if holds:
        with server._lock:
            for node in list(server._prefix_entry_nodes):
                server._evict_prefix_node(node, "pressure")
            for node in list(server._prefix_host_nodes):
                server._drop_host_record_locked(node)
        if cache.free_pages() != acct["pages_total"]:
            fail(f"{context}: {acct['pages_total'] - cache.free_pages()}"
                 f" pages still held after force-evicting the registry")


# Every key a version-1 flight-recorder bundle must carry
# (models/serving.py flight_bundle). Completeness is the invariant:
# a post-mortem missing its books or its SLO state is worse than no
# post-mortem, because it looks authoritative.
_BUNDLE_V1_KEYS = frozenset((
    "bundle_version", "reason", "degraded", "metrics", "slo",
    "occupancy_tail", "journal", "config", "config_fingerprint",
    "trace_tail", "page_accounting",
))


def _check_bundle(server, cache, fail, *, context: str,
                  timeline: bool) -> None:
    """Rung-25 flight-recorder completeness after every round: the
    bundle must be schema-complete, JSON-serialisable, and its
    SLO/burn state and page books must agree with a fresh stats()
    snapshot — the bundle claims to BE the server's final state, so
    any drift between the two means the single-lock assembly broke."""
    import json as _json

    bundle = server.flight_bundle()
    missing = _BUNDLE_V1_KEYS - set(bundle)
    if missing:
        fail(f"{context}: bundle incomplete — missing "
             f"{sorted(missing)}")
    if bundle["bundle_version"] != 1:
        fail(f"{context}: unknown bundle_version "
             f"{bundle['bundle_version']!r}")
    try:
        _json.dumps(bundle)
    except (TypeError, ValueError) as e:
        fail(f"{context}: bundle is not JSON-serialisable: {e}")
    if not bundle["config_fingerprint"]:
        fail(f"{context}: bundle config_fingerprint is empty")
    if bundle["slo"] is None:
        fail(f"{context}: bundle has no SLO state with the engine on")
    # The campaign's server runs with an occupancy ring, and a request
    # that completed crossed a quiescent boundary — from then on
    # (``timeline``) the tail must not be empty. Before it the ring
    # may be: a raise in the first boundary's checkpoint poisons the
    # pool ahead of the boundary's sample.
    if timeline and not bundle["occupancy_tail"]:
        fail(f"{context}: bundle occupancy_tail is empty")
    books = bundle["page_accounting"]
    if books is None:
        fail(f"{context}: bundle page books absent (cache exposes "
             "page_accounting)")
    if books != cache.page_accounting():
        fail(f"{context}: bundle page books diverge from the live "
             f"pool: {books} vs {cache.page_accounting()}")
    # SLO/burn agreement with the server's own metrics snapshot: the
    # pool is quiescent after settle, so the flat slo_* gauges stats()
    # exports must be exactly what the bundle froze.
    stats = server.stats()
    for key in stats:
        if key.startswith("slo_") and bundle["metrics"].get(key) != stats[key]:
            fail(f"{context}: bundle {key}={bundle['metrics'].get(key)!r}"
                 f" != live stats {stats[key]!r}")
