"""Runtime lockdep witness tests: a seeded inverted acquisition trips
the witness deterministically; the correct-order twin does not; the
edge-recording semantics (try-acquire, re-entrancy, Condition.wait)
match real deadlock risk.

All inversions here are *seeded* — locks are taken in both orders on
purpose, with joins between the two orders so nothing can actually
deadlock; lockdep-style, the witness trips on the second ORDER, not on
an unlucky interleaving.
"""

import threading

import pytest

from pilosa_tpu.testing import lockwitness
from pilosa_tpu.testing.lockwitness import LockOrderInversion


def _two_locks():
    # distinct source lines => distinct allocation-site keys
    a = threading.Lock()
    b = threading.Lock()
    return a, b


def _join(t):
    t.join(timeout=10.0)
    assert not t.is_alive(), "worker thread hung"


class TestSeededInversion:
    def test_single_thread_inversion_raises(self):
        with lockwitness.active(mode="raise"):
            a, b = _two_locks()
            with a:
                with b:
                    pass
            with b:
                with pytest.raises(LockOrderInversion) as exc:
                    with a:
                        pass
            msg = str(exc.value)
            assert "lock order inversion" in msg
            assert "test_lockwitness.py" in msg  # witness sites named

    def test_two_thread_inversion_raises(self):
        """Thread takes A then B and finishes; main thread then takes
        B then A — deterministic (join between the orders), no actual
        deadlock possible, witness still trips."""
        with lockwitness.active(mode="raise"):
            a, b = _two_locks()

            def worker():
                with a:
                    with b:
                        pass

            t = threading.Thread(target=worker, daemon=True)
            t.start()
            _join(t)
            with b:
                with pytest.raises(LockOrderInversion):
                    with a:
                        pass
            assert len(lockwitness.findings()) == 1

    def test_correct_order_twin_is_clean(self):
        with lockwitness.active(mode="raise"):
            a, b = _two_locks()

            def worker():
                with a:
                    with b:
                        pass

            t = threading.Thread(target=worker, daemon=True)
            t.start()
            _join(t)
            with a:  # same global order: A before B everywhere
                with b:
                    pass
            assert lockwitness.findings() == []
            assert lockwitness.order_graph()  # the A->B edge was seen

    def test_trap_releases_the_lock(self):
        """Raise-mode must hand the inner lock back, or the victim's
        peers hang forever on a lock whose with-body never ran."""
        with lockwitness.active(mode="raise"):
            a, b = _two_locks()
            with a:
                with b:
                    pass
            with b:
                with pytest.raises(LockOrderInversion):
                    a.acquire()
            assert not a.locked()
            assert not b.locked()


class TestLogMode:
    def test_log_mode_records_without_raising(self):
        with lockwitness.active(mode="log"):
            a, b = _two_locks()
            with a:
                with b:
                    pass
            with b:
                with a:  # inversion: recorded, not raised
                    pass
            [inv] = lockwitness.findings()
            assert "then" in inv["this_order"]
            assert "then" in inv["prior_order"]

    def test_pair_reported_once(self):
        with lockwitness.active(mode="log"):
            a, b = _two_locks()
            for _ in range(3):
                with a:
                    with b:
                        pass
                with b:
                    with a:
                        pass
            assert len(lockwitness.findings()) == 1


class TestEdgeSemantics:
    def test_try_acquire_records_no_edge(self):
        """A failed-or-timed attempt cannot wait forever, so holding A
        while TRY-acquiring B must not poison the A->B order."""
        with lockwitness.active(mode="raise"):
            a, b = _two_locks()
            with a:
                assert b.acquire(blocking=False)
                b.release()
            with b:
                with a:  # would invert if the try-acquire made an edge
                    pass
            assert lockwitness.findings() == []

    def test_successful_try_acquire_still_enters_held_set(self):
        """Edges FROM a held try-acquired lock are real: a later
        blocking acquire under it can deadlock against the reverse."""
        with lockwitness.active(mode="raise"):
            a, b = _two_locks()
            assert b.acquire(blocking=False)
            with a:  # records edge B->A
                pass
            b.release()
            with a:
                with pytest.raises(LockOrderInversion):
                    b.acquire()

    def test_rlock_reentrancy_is_silent(self):
        with lockwitness.active(mode="raise"):
            r = threading.RLock()
            with r:
                with r:
                    pass
            assert lockwitness.findings() == []
            assert lockwitness.order_graph() == {}

    def test_same_allocation_site_nesting_is_reentrant(self):
        """Two instances of one class share a per-class key (allocation
        site); nesting them records nothing rather than a self-edge."""
        with lockwitness.active(mode="raise"):
            def make():
                return threading.Lock()

            x, y = make(), make()
            with x:
                with y:
                    pass
            assert lockwitness.order_graph() == {}

    def test_condition_wait_keeps_held_set_honest(self):
        """Condition.wait releases the underlying lock through the
        wrapper, so an edge formed while waiting must not claim the
        condition's lock was held."""
        with lockwitness.active(mode="raise"):
            lk = threading.RLock()
            cond = threading.Condition(lk)
            other = threading.Lock()
            started = threading.Event()

            def waiter():
                with cond:
                    started.set()
                    cond.wait(timeout=10.0)

            t = threading.Thread(target=waiter, daemon=True)
            t.start()
            assert started.wait(timeout=10.0)
            # while the waiter sleeps inside wait() (cond lock RELEASED),
            # acquire other->cond-lock; if wait() leaked the held set this
            # order would later invert against the waiter's cond->...
            with other:
                with cond:
                    cond.notify_all()
            _join(t)
            # waiter re-acquired via _acquire_restore; no inversions
            assert lockwitness.findings() == []


class TestFinalizerReentrancy:
    def test_lock_taken_from_inside_the_guard_does_not_deadlock(self):
        """``weakref.finalize(field, budget.release, ...)`` runs wherever
        the collector fires — also at an allocation inside the witness's
        own critical section, on a thread that holds a witnessed lock.
        The finalizer's lock acquisition then re-enters the witness on
        the same thread; with a plain guard that thread waited for itself
        and every later witnessed acquisition in the process queued up
        behind it (a tier-1 run that stops with no CPU use).  The
        collector is stood in for by a held-site whose formatting, which
        happens under the guard, takes a lock."""
        with lockwitness.active(mode="raise"):
            a, b = _two_locks()
            c = threading.Lock()

            class CollectorFiresHere:
                fired = False

                def __format__(self, spec):
                    if not self.fired:
                        self.fired = True
                        with c:
                            pass
                    return "site"

            def worker():
                with a:
                    held = lockwitness._state.held()
                    held[-1] = (held[-1][0], CollectorFiresHere())
                    with b:
                        pass

            t = threading.Thread(target=worker, daemon=True)
            t.start()
            t.join(timeout=10.0)
            if t.is_alive():
                # the stuck worker owns the guard for good: give the rest
                # of the session a fresh one before failing
                lockwitness._state.guard = lockwitness._real_rlock()
                pytest.fail("re-entrant acquisition under the guard deadlocked")
            assert lockwitness.findings() == []
            assert lockwitness.stats()["edges"] >= 2  # a->b and a->c


class TestInstallScoping:
    def test_out_of_scope_allocations_pass_through(self):
        with lockwitness.active(mode="raise"):
            import queue

            q = queue.Queue()  # stdlib allocates its own locks
            q.put(1)
            assert q.get() == 1

    def test_active_restores_prior_state(self):
        before = lockwitness.stats()["installed"]
        with lockwitness.active(mode="log"):
            assert lockwitness.stats()["mode"] == "log"
        assert lockwitness.stats()["installed"] == before

    def test_stats_shape(self):
        with lockwitness.active(mode="raise"):
            a, b = _two_locks()
            with a:
                with b:
                    pass
            s = lockwitness.stats()
            assert s["mode"] == "raise"
            assert s["witnessedAcquires"] >= 2
            assert s["edges"] == 1
            assert s["inversions"] == 0
