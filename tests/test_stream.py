"""Streaming sampling service (repro.serve.stream).

CI-blocking contracts:

- the batching-window policy launches for the right *reason*: fill when a
  cohort hits ``max_requests_per_launch``, slack when a deadline'd
  member's remaining budget approaches the measured launch cost, window
  when a deadline-less member has waited ``max_batch_window_ms``;
- launch order is EDF with priority tiers breaking ties;
- streamed results are bit-identical to the standalone padded engine call
  (streaming changes launch timing, never packing semantics);
- per-tenant token buckets reject over-quota submits with an
  :class:`AdmissionError` naming the violated limit;
- a failed cohort launch fails exactly its unserved members' futures,
  with a :class:`DrainError` carrying the partial results.

Everything except the thread-mode smoke runs in the deterministic driving
mode: ``start=False`` + an injected fake clock + ``poll()``/``flush()``,
so every policy decision is replayable.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import algorithms as alg
from repro.core.engine import random_walk
from repro.graph import powerlaw_graph
from repro.graph.partition import partition_by_vertex_range
from repro.serve import (
    AdmissionError,
    DrainError,
    Priority,
    SamplingService,
    ServiceConfig,
    StreamConfig,
    StreamingSamplingService,
    TenantQuota,
)
from repro.serve.queue import _pow2_bucket


@pytest.fixture(scope="module")
def graph():
    return powerlaw_graph(400, seed=3, weighted=True)


class FakeClock:
    """Injectable monotonic clock: time moves only when the test says so."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


def make_stream(graph, config=None, svc_config=None, **svc_kwargs):
    clk = FakeClock()
    svc = SamplingService(
        graph, backend="reference", key=jax.random.PRNGKey(7),
        config=svc_config, **svc_kwargs,
    )
    stream = StreamingSamplingService(svc, config, clock=clk, start=False)
    return stream, clk


class TestWindowPolicy:
    def test_window_trigger(self, graph):
        """Deadline-less requests wait exactly the batching window, then
        launch together (one cohort, reason "window")."""
        stream, clk = make_stream(graph, StreamConfig(max_batch_window_ms=20))
        f1 = stream.submit([0, 1, 2], depth=4, spec=alg.deepwalk())
        clk.t = 0.005
        f2 = stream.submit([3, 4], depth=4, spec=alg.deepwalk())
        clk.t = 0.019  # window not elapsed for either
        assert stream.poll() == 0 and stream.pending == 2
        clk.t = 0.0201  # f1's window elapsed; f2 rides along (same cohort)
        assert stream.poll() == 1
        assert stream.pending == 0 and f1.done() and f2.done()
        assert f1.latency.reason == "window"
        assert f1.result().walks.shape == (3, 5)
        assert f2.result().walks.shape == (2, 5)

    def test_fill_trigger(self, graph):
        """A cohort that reaches max_requests_per_launch launches at once —
        waiting longer buys nothing."""
        stream, clk = make_stream(
            graph, StreamConfig(max_batch_window_ms=1000),
            svc_config=ServiceConfig(max_requests_per_launch=3),
        )
        futs = [stream.submit([i], depth=4, spec=alg.deepwalk()) for i in range(3)]
        assert stream.poll() == 1  # no clock advance needed
        assert all(f.done() for f in futs)
        assert futs[0].latency.reason == "fill"

    def test_slack_trigger(self, graph):
        """A deadline'd request launches when its remaining slack shrinks to
        slack_factor x the estimated launch cost — not before."""
        stream, clk = make_stream(
            graph,
            StreamConfig(
                max_batch_window_ms=1000, slack_factor=2.0,
                launch_cost_prior_ms=10.0,
            ),
        )
        f = stream.submit([0, 1], depth=4, spec=alg.deepwalk(), deadline_ms=100)
        clk.t = 0.079  # launch point is 100ms - 2x10ms = 80ms
        assert stream.poll() == 0
        clk.t = 0.081
        assert stream.poll() == 1
        assert f.latency.reason == "slack"
        assert f.latency.deadline_met is True

    def test_loose_deadline_overrides_window(self, graph):
        """An explicit deadline looser than the window keeps the request
        batching past max_batch_window_ms (the window is the *implied* SLO,
        not a cap on explicit ones)."""
        stream, clk = make_stream(
            graph,
            StreamConfig(
                max_batch_window_ms=20, slack_factor=1.0,
                launch_cost_prior_ms=10.0,
            ),
        )
        stream.submit([0], depth=4, spec=alg.deepwalk(), deadline_ms=500)
        clk.t = 0.100  # well past the window, well before 500ms - 10ms
        assert stream.poll() == 0
        clk.t = 0.491
        assert stream.poll() == 1

    def test_batching_false_launches_per_request(self, graph):
        """The open-loop baseline mode: every request launches immediately
        in its own cohort."""
        stream, clk = make_stream(
            graph, StreamConfig(batching=False, max_batch_window_ms=1000)
        )
        f1 = stream.submit([0, 1], depth=4, spec=alg.deepwalk())
        f2 = stream.submit([2, 3], depth=4, spec=alg.deepwalk())
        assert stream.poll() == 2  # no co-batching despite identical key
        assert f1.latency.reason == "immediate"
        assert f2.latency.reason == "immediate"
        assert stream.stats.stream_launches == 2

    def test_flush_launches_everything(self, graph):
        stream, clk = make_stream(graph, StreamConfig(max_batch_window_ms=1000))
        f = stream.submit([0], depth=4, spec=alg.deepwalk())
        assert stream.poll() == 0  # not due
        assert stream.flush() == 1
        assert f.latency.reason == "flush"


class TestLaunchOrder:
    def test_edf_across_cohorts(self, graph):
        """Among due cohorts, the earliest effective deadline launches
        first (module-level hook specs => distinct cohort keys)."""
        stream, clk = make_stream(
            graph, StreamConfig(slack_factor=1.0, launch_cost_prior_ms=1.0)
        )
        fa = stream.submit([0], depth=4, spec=alg.deepwalk(), deadline_ms=100)
        fb = stream.submit(
            [1], depth=4, spec=alg.weighted_random_walk(), deadline_ms=50
        )
        clk.t = 0.200  # both overdue
        assert stream.poll() == 2
        order = [lat.request_id for lat in stream.stats.stream_latencies]
        assert order == [fb.request_id, fa.request_id]

    def test_priority_breaks_deadline_ties(self, graph):
        """Equal deadlines: INTERACTIVE preempts STANDARD even though it
        arrived later."""
        stream, clk = make_stream(
            graph, StreamConfig(slack_factor=1.0, launch_cost_prior_ms=1.0)
        )
        fa = stream.submit([0], depth=4, spec=alg.deepwalk(), deadline_ms=50)
        fb = stream.submit(
            [1], depth=4, spec=alg.weighted_random_walk(), deadline_ms=50,
            priority=Priority.INTERACTIVE,
        )
        clk.t = 0.200
        assert stream.poll() == 2
        order = [lat.request_id for lat in stream.stats.stream_latencies]
        assert order == [fb.request_id, fa.request_id]
        assert fb.latency.tier == int(Priority.INTERACTIVE)

    def test_fifo_breaks_full_ties(self, graph):
        """Same deadline, same priority: arrival order decides."""
        stream, clk = make_stream(
            graph, StreamConfig(slack_factor=1.0, launch_cost_prior_ms=1.0)
        )
        fa = stream.submit([0], depth=4, spec=alg.deepwalk(), deadline_ms=50)
        fb = stream.submit(
            [1], depth=4, spec=alg.weighted_random_walk(), deadline_ms=50
        )
        clk.t = 0.200
        stream.poll()
        order = [lat.request_id for lat in stream.stats.stream_latencies]
        assert order == [fa.request_id, fb.request_id]


class TestStreamedParity:
    @pytest.mark.parametrize("backend", ["reference", "pallas"])
    def test_streamed_matches_standalone_padded_call(self, graph, backend):
        """The PR 4 guarantee, lifted to streaming: a streamed request's
        walks are bit-identical to the standalone ``random_walk`` call at
        the padded geometry, regardless of who shared its launch."""
        g = graph
        clk = FakeClock()
        svc = SamplingService(g, backend=backend, key=jax.random.PRNGKey(7))
        stream = StreamingSamplingService(
            svc, StreamConfig(max_batch_window_ms=10), clock=clk, start=False
        )
        rng = np.random.default_rng(5)
        subs = []
        for i in range(4):
            seeds = rng.integers(0, g.num_vertices, int(rng.integers(3, 20)))
            key = jax.random.fold_in(jax.random.PRNGKey(42), i)
            fut = stream.submit(
                seeds, depth=6, spec=alg.deepwalk(), key=key,
                deadline_ms=float(rng.integers(5, 100)),
            )
            subs.append((fut, seeds, key))
            clk.t += 0.003
        clk.t += 1.0
        stream.poll()
        cfg = svc.config
        for fut, seeds, key in subs:
            width = _pow2_bucket(len(seeds), cfg.min_walker_bucket)
            depth_b = _pow2_bucket(6, cfg.min_depth_bucket)
            row = np.full((width,), -1, np.int32)
            row[: len(seeds)] = seeds
            solo = random_walk(
                g, jnp.asarray(row), key, depth=depth_b, spec=alg.deepwalk(),
                max_degree=g.max_degree(), backend=backend,
            )
            expect = np.asarray(solo.walks)[: len(seeds), :7]
            np.testing.assert_array_equal(fut.result().walks, expect)


class TestQuota:
    def test_over_quota_rejected_with_named_limit(self, graph):
        stream, clk = make_stream(
            graph,
            StreamConfig(
                tenant_quotas={"acme": TenantQuota(walkers_per_s=10, burst_walkers=20)}
            ),
        )
        stream.submit(np.arange(16), depth=4, spec=alg.deepwalk(), tenant="acme")
        with pytest.raises(AdmissionError) as ei:
            stream.submit(np.arange(16), depth=4, spec=alg.deepwalk(), tenant="acme")
        msg = str(ei.value)
        assert "tenant_quotas['acme'].walkers_per_s=10" in msg
        assert "burst_walkers=20" in msg
        assert stream.stats.stream_quota_rejections == 1
        # unmetered tenants (and tenant-less requests) are unaffected
        stream.submit(np.arange(16), depth=4, spec=alg.deepwalk(), tenant="other")
        stream.submit(np.arange(16), depth=4, spec=alg.deepwalk())
        assert stream.pending == 3
        stream.flush()

    def test_bucket_refills_over_time(self, graph):
        stream, clk = make_stream(
            graph,
            StreamConfig(
                tenant_quotas={"t": TenantQuota(walkers_per_s=100, burst_walkers=16)}
            ),
        )
        stream.submit(np.arange(16), depth=4, spec=alg.deepwalk(), tenant="t")
        with pytest.raises(AdmissionError):
            stream.submit(np.arange(16), depth=4, spec=alg.deepwalk(), tenant="t")
        clk.t = 0.16  # 100 walkers/s x 0.16s = 16 tokens back
        stream.submit(np.arange(16), depth=4, spec=alg.deepwalk(), tenant="t")
        assert stream.pending == 2
        stream.flush()

    def test_backpressure_limits_apply_to_backlog(self, graph):
        stream, clk = make_stream(
            graph, StreamConfig(max_batch_window_ms=1000),
            svc_config=ServiceConfig(max_pending_requests=2),
        )
        stream.submit([0], depth=4, spec=alg.deepwalk())
        stream.submit([1], depth=4, spec=alg.deepwalk())
        with pytest.raises(AdmissionError, match="max_pending_requests=2"):
            stream.submit([2], depth=4, spec=alg.deepwalk())
        stream.flush()  # launching frees capacity
        stream.submit([2], depth=4, spec=alg.deepwalk())
        stream.flush()


class TestDelivery:
    def test_partial_failure_isolates_members(self, graph, monkeypatch):
        """Sequential-mode cohort: the member served before the failure gets
        its result; the failing member's future raises a DrainError carrying
        the partial results; other cohorts are untouched."""
        stream, clk = make_stream(
            graph, svc_config=ServiceConfig(fuse=False)
        )
        f1 = stream.submit([0, 1], depth=4, spec=alg.deepwalk())
        f2 = stream.submit([2, 3], depth=4, spec=alg.deepwalk())  # same cohort
        f3 = stream.submit([4, 5], depth=4, spec=alg.node2vec())  # separate
        import repro.serve.service as service_mod

        real = service_mod.random_walk
        calls = {"n": 0}

        def flaky(*args, **kwargs):
            calls["n"] += 1
            if calls["n"] == 2:
                raise RuntimeError("injected launch failure")
            return real(*args, **kwargs)

        monkeypatch.setattr(service_mod, "random_walk", flaky)
        stream.flush()
        assert f1.result().walks.shape == (2, 5)  # served before the failure
        with pytest.raises(DrainError) as ei:
            f2.result()
        assert "1/2 cohort members completed" in str(ei.value)
        assert sorted(ei.value.completed) == [f1.request_id]
        assert isinstance(ei.value.__cause__, RuntimeError)
        assert f3.result().walks.shape == (2, 5)  # other cohort unaffected
        assert stream.stats.stream_failed_requests == 1

    def test_fused_failure_fails_whole_cohort_only(self, graph, monkeypatch):
        stream, clk = make_stream(graph)
        f1 = stream.submit([0, 1], depth=4, spec=alg.deepwalk())
        f2 = stream.submit([2, 3], depth=4, spec=alg.node2vec())
        import repro.serve.service as service_mod

        def boom(*args, **kwargs):
            raise RuntimeError("injected launch failure")

        monkeypatch.setattr(service_mod, "random_walk_segments", boom)
        stream.flush()
        # both cohorts route through the (broken) fused entry point; each
        # failure is scoped to its own cohort and carries no partial results
        for f in (f1, f2):
            exc = f.exception()
            assert isinstance(exc, DrainError)
            assert "0/1 cohort members completed" in str(exc)
            assert exc.completed == {}
        assert stream.stats.stream_failed_requests == 2

    def test_done_callbacks(self, graph):
        stream, clk = make_stream(graph)
        seen = []
        f = stream.submit([0], depth=4, spec=alg.deepwalk())
        f.add_done_callback(lambda fut: seen.append(("pre", fut.request_id)))
        stream.flush()
        f.add_done_callback(lambda fut: seen.append(("post", fut.request_id)))
        assert seen == [("pre", f.request_id), ("post", f.request_id)]

    def test_result_timeout(self, graph):
        stream, clk = make_stream(graph, StreamConfig(max_batch_window_ms=1000))
        f = stream.submit([0], depth=4, spec=alg.deepwalk())
        with pytest.raises(TimeoutError):
            f.result(timeout=0.01)
        stream.flush()
        assert f.result(timeout=0).walks.shape == (1, 5)


class TestLifecycle:
    def test_close_flush_serves_backlog(self, graph):
        stream, clk = make_stream(graph, StreamConfig(max_batch_window_ms=1000))
        f = stream.submit([0], depth=4, spec=alg.deepwalk())
        stream.close()
        assert f.result(timeout=0).walks.shape == (1, 5)

    def test_close_without_flush_cancels(self, graph):
        stream, clk = make_stream(graph, StreamConfig(max_batch_window_ms=1000))
        f = stream.submit([0], depth=4, spec=alg.deepwalk())
        stream.close(flush=False)
        with pytest.raises(DrainError, match="cancelled"):
            f.result(timeout=0)
        assert stream.pending == 0

    def test_submit_after_close_rejected(self, graph):
        stream, clk = make_stream(graph)
        stream.close()
        with pytest.raises(AdmissionError, match="closed"):
            stream.submit([0], depth=4, spec=alg.deepwalk())


class TestLatencyAccounting:
    def test_queue_and_total_latency_from_clock(self, graph):
        stream, clk = make_stream(graph, StreamConfig(max_batch_window_ms=1000))
        f = stream.submit([0], depth=4, spec=alg.deepwalk())
        clk.t = 0.050
        stream.flush()
        lat = f.latency
        assert lat.queue_ms == pytest.approx(50.0)
        assert lat.total_ms == pytest.approx(50.0)  # fake clock: 0ms launch
        assert lat.deadline_met is None
        assert stream.stats.stream_requests == 1
        assert stream.stats.stream_launches == 1
        assert stream.stats.stream_latencies == [lat]

    def test_blocked_behind_the_launch_in_flight(self, graph, monkeypatch):
        """A cohort that falls due while another launch holds the device
        counts the wait from its due time to its launch start as
        ``blocked_ms``; ``queue_ms - blocked_ms`` is its batching window."""
        stream, clk = make_stream(graph, StreamConfig(max_batch_window_ms=20))
        svc = stream._svc
        real = svc._run_cohort

        restart = alg.random_walk_with_restart(0.5)
        late = []

        def slow(cohort, out):
            clk.t += 0.050
            if not late:  # arrives mid-launch, joining b's cohort, already due
                late.append(stream.submit([3], depth=4, spec=restart))
            clk.t += 0.050  # every launch takes 100 ms
            return real(cohort, out)

        monkeypatch.setattr(svc, "_run_cohort", slow)
        fa = stream.submit([0, 1], depth=4, spec=alg.deepwalk())  # due at 20 ms
        clk.t = 0.010
        fb = stream.submit([2], depth=4, spec=restart)  # due at 30 ms
        clk.t = 0.020
        assert stream.poll() == 2  # a launches at 20 ms and holds to 120 ms, then b
        a, b, c = fa.latency, fb.latency, late[0].latency
        assert (a.reason, b.reason, c.reason) == ("window", "window", "window")
        assert a.queue_ms == pytest.approx(20.0) and a.blocked_ms == pytest.approx(0.0)
        assert b.queue_ms == pytest.approx(110.0)
        assert b.blocked_ms == pytest.approx(90.0)
        assert b.queue_ms - b.blocked_ms == pytest.approx(20.0)
        # submitted at 70 ms into a cohort due since 30 ms: all its wait is blocked
        assert c.queue_ms == pytest.approx(50.0) and c.blocked_ms == pytest.approx(50.0)

    def test_blocked_counts_from_the_fill(self, graph):
        """A cohort is due the moment it fills: what it waits after that is
        blocked time, what its first member waited before it is the
        policy's."""
        stream, clk = make_stream(
            graph, StreamConfig(max_batch_window_ms=1000),
            svc_config=ServiceConfig(max_requests_per_launch=2),
        )
        f1 = stream.submit([0], depth=4, spec=alg.deepwalk())
        clk.t = 0.005
        f2 = stream.submit([1], depth=4, spec=alg.deepwalk())  # fills: due now
        clk.t = 0.030
        assert stream.poll() == 1
        assert f1.latency.reason == "fill"
        assert f1.latency.blocked_ms == pytest.approx(25.0)
        assert f1.latency.queue_ms - f1.latency.blocked_ms == pytest.approx(5.0)
        assert f2.latency.blocked_ms == pytest.approx(25.0)
        assert f2.latency.queue_ms - f2.latency.blocked_ms == pytest.approx(0.0)

    def test_deadline_miss_counted(self, graph):
        stream, clk = make_stream(graph)
        f = stream.submit([0], depth=4, spec=alg.deepwalk(), deadline_ms=10)
        clk.t = 1.0  # poll far too late: result lands past the deadline
        stream.poll()
        assert f.latency.deadline_met is False
        assert stream.stats.stream_deadline_misses == 1
        assert f.result(timeout=0).walks.shape == (1, 5)  # still served

    def test_launch_cost_ema(self, graph, monkeypatch):
        """The slack trigger's cost estimate tracks measured launch wall
        time per cohort key (EMA, alpha=0.25 here)."""
        stream, clk = make_stream(
            graph, StreamConfig(launch_cost_prior_ms=25.0, launch_cost_alpha=0.25)
        )
        svc = stream._svc
        real = svc._run_cohort
        advance = {"by": 0.008}

        def timed(cohort, out):
            clk.t += advance["by"]
            return real(cohort, out)

        monkeypatch.setattr(svc, "_run_cohort", timed)
        spec = alg.deepwalk()
        assert stream.launch_cost_ms(spec, depth=4, width=1) == pytest.approx(25.0)
        stream.submit([0], depth=4, spec=spec)
        stream.flush()
        assert stream.launch_cost_ms(spec, depth=4, width=1) == pytest.approx(8.0)
        advance["by"] = 0.004
        stream.submit([1], depth=4, spec=spec)
        stream.flush()
        # EMA: 0.25 x 4ms + 0.75 x 8ms = 7ms
        assert stream.launch_cost_ms(spec, depth=4, width=1) == pytest.approx(7.0)


class TestPlacements:
    def test_oom_streaming_merges_depths(self, graph):
        """Partitioned placement: streamed heterogeneous-depth requests of
        one program share a single frontier-queue drain."""
        g = graph
        parts = partition_by_vertex_range(g, 4)
        clk = FakeClock()
        svc = SamplingService(
            partitions=parts, total_vertices=g.num_vertices,
            backend="reference", oom_chunk=128,
        )
        stream = StreamingSamplingService(svc, clock=clk, start=False)
        fa = stream.submit(np.arange(30), depth=4, spec=alg.deepwalk())
        fb = stream.submit(np.arange(20), depth=9, spec=alg.deepwalk())
        clk.t = 1.0
        assert stream.poll() == 1
        assert svc.stats.oom_launches == 1
        assert fa.result(timeout=0).walks.shape == (30, 5)
        assert fb.result(timeout=0).walks.shape == (20, 10)

    def test_sharded_streaming(self, graph):
        g = graph
        mesh = jax.make_mesh((1,), ("data",))
        clk = FakeClock()
        svc = SamplingService(
            g, mesh=mesh, placement="sharded", backend="reference",
        )
        stream = StreamingSamplingService(svc, clock=clk, start=False)
        f = stream.submit(np.arange(16), depth=5, spec=alg.deepwalk())
        clk.t = 1.0
        assert stream.poll() == 1
        assert svc.stats.sharded_launches == 1
        assert f.result(timeout=0).walks.shape == (16, 6)


class TestThreadMode:
    def test_background_scheduler_serves_bursts(self, graph):
        """The production mode: a daemon thread drives the same policy.
        Real clock — only liveness and delivery are asserted here; policy
        details are covered by the deterministic tests above."""
        g = graph
        svc = SamplingService(g, backend="reference", key=jax.random.PRNGKey(3))
        with StreamingSamplingService(
            svc, StreamConfig(max_batch_window_ms=5)
        ) as stream:
            futs = [
                stream.submit(
                    [i, i + 1], depth=4, spec=alg.deepwalk(),
                    deadline_ms=30_000,
                )
                for i in range(4)
            ]
            for f in futs:
                assert f.result(timeout=120).walks.shape == (2, 5)
        assert stream.pending == 0
        assert stream.stats.stream_requests == 4
        assert len(stream.stats.stream_latencies) == 4
