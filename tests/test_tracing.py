"""The serving path's tracer (``serving/tracing.py``): off, its span
sites record nothing and make no profiler annotation; on, spans nest
under ``engine.step``, token writes carry their request and commit
time (and end when the client walks away), and an executor call that
builds a bucket is marked once."""

import asyncio
import json
from types import SimpleNamespace

import jax
import pytest

from repro.serving.frontend import AsyncFrontend
from repro.serving import tracing
from repro.serving.tracing import PREFIX, Tracer

from test_frontend import make_engine, spin

STEP_CHILDREN = ("scheduler.plan", "executor.prepare", "executor.dispatch",
                 "executor.wait", "executor.build", "scheduler.commit")


class TickClock:
    """A clock that moves one tick each time it is read."""

    def __init__(self):
        self.t, self.reads = 0.0, 0

    def __call__(self) -> float:
        self.reads += 1
        self.t += 1.0
        return self.t


@pytest.fixture
def annotations(monkeypatch):
    """The profiler annotations the tracer makes: ``names`` in the order
    made, ``open`` the number entered and not yet exited."""
    made = SimpleNamespace(names=[], open=0)

    class Recorder:
        def __init__(self, name, **kw):
            made.names.append(name)

        def __enter__(self):
            made.open += 1
            return self

        def __exit__(self, *exc):
            made.open -= 1
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    return made


def traced(clock=None):
    tr = Tracer(clock or TickClock())
    tr.enabled = True
    return tr


def serve(eng, prompts, max_new_tokens=3, steps=200):
    for p in prompts:
        eng.submit(p, max_new_tokens)
    for _ in range(steps):
        if not eng.scheduler.waiting and not eng.scheduler.running:
            return
        eng.step()
    raise AssertionError("requests did not finish")


def by_idx(tr):
    return {s.idx: s for s in tr.spans}


class TestOff:
    def test_span_sites_record_nothing(self, annotations):
        clock = TickClock()
        eng, _ = make_engine(tracer=Tracer(clock))
        fe = AsyncFrontend(eng)
        serve(eng, [[1, 2, 3, 4, 5], [6, 7, 8]], max_new_tokens=4)
        assert eng.scheduler.metrics["steps"] >= 4
        tr = eng.tracer
        assert not tr.spans and tr._next == 0
        assert eng.last_commit_end is None
        assert annotations.names == [] and clock.reads == 0
        assert fe.stats()["steps"] == eng.scheduler.metrics["steps"]

    def test_stream_events_carry_no_commit_time(self):
        async def main():
            eng, _ = make_engine()
            fe = AsyncFrontend(eng)
            events = []

            async def consume():
                async for ev in fe.stream([1, 2, 3], 3):
                    events.append(ev)
            task = asyncio.ensure_future(consume())
            await spin()
            while fe.busy and not task.done():
                fe.pump()
                await spin()
            await task
            assert [e.committed_at for e in events] == [None] * 4
            assert not eng.tracer.spans

        asyncio.run(main())


class TestOn:
    def test_span_tree_nests_under_engine_step(self, annotations):
        eng, _ = make_engine(tracer=traced())
        serve(eng, [[1, 2, 3, 4, 5], [6, 7, 8]], max_new_tokens=4)
        tr = eng.tracer
        spans = by_idx(tr)
        steps = [s for s in tr.spans if s.name == "engine.step"]
        assert len(steps) == eng.scheduler.metrics["steps"]
        assert [s.step for s in steps] == list(range(1, len(steps) + 1))
        assert all(s.parent == -1 for s in steps)
        for s in tr.spans:
            if s.name == "engine.step":
                continue
            assert s.name in STEP_CHILDREN
            parent = spans[s.parent]
            assert parent.name == "engine.step" and parent.step == s.step
            assert parent.start < s.start <= s.end < parent.end
        # each step: plan, prepare, dispatch, wait, commit, in order
        for st in steps:
            kids = sorted((s for s in tr.spans if s.parent == st.idx
                           and s.name != "executor.build"),
                          key=lambda s: s.idx)
            assert [k.name for k in kids] == [
                "scheduler.plan", "executor.prepare", "executor.dispatch",
                "executor.wait", "scheduler.commit"]
            assert all(a.end <= b.start for a, b in zip(kids, kids[1:]))
        # every span but the build is also a profiler annotation
        want = sorted(PREFIX + s.name for s in tr.spans
                      if s.name != "executor.build")
        assert sorted(annotations.names) == want
        assert annotations.open == 0
        assert eng.last_commit_end == max(
            s.end for s in tr.spans if s.name == "scheduler.commit")

    def test_build_span_per_new_bucket_none_on_a_hit(self):
        eng, _ = make_engine(tracer=traced())
        serve(eng, [[1, 2, 3, 4, 5, 6, 7, 8, 9], [3, 4]], max_new_tokens=3)
        tr, ex = eng.tracer, eng.executor
        builds = [s for s in tr.spans if s.name == "executor.build"]
        assert len(builds) == ex.compile_count >= 2
        assert [(b.attrs["t_bucket"], b.attrs["p_bucket"])
                for b in builds] == ex.compiled_buckets
        assert eng.metrics["bucket_compiles"] == len(builds)
        spans = by_idx(tr)
        for b in builds:
            step = spans[b.parent]
            assert step.name == "engine.step"
            kid = {s.name: s for s in tr.spans if s.parent == step.idx}
            # the build covers the call's host work up to the device wait
            assert b.start < kid["executor.prepare"].start
            assert kid["executor.dispatch"].end < b.end \
                < kid["executor.wait"].start
        # the same shapes again: the jit cache hits, nothing is built
        serve(eng, [[1, 2, 3, 4, 5, 6, 7, 8, 9], [3, 4]], max_new_tokens=3)
        assert [s for s in tr.spans if s.name == "executor.build"] == builds
        assert eng.metrics["bucket_compiles"] == len(builds)

    def test_ring_keeps_the_newest_spans(self):
        tr = traced()
        n = tracing.CAPACITY + 3
        for i in range(n):
            tr.record("engine.step", i, i + 1)
        assert len(tr.spans) == tracing.CAPACITY
        assert tr.spans[0].idx == 3 and tr.spans[-1].idx == n - 1

    def test_exception_inside_a_span_leaves_no_open_parent(self):
        tr = traced()
        with pytest.raises(RuntimeError):
            with tr.span("engine.step"):
                tr.begin("executor.prepare")    # never ended
                raise RuntimeError("device fault")
        with tr.span("engine.step"):
            pass
        assert [s.parent for s in tr.spans] == [-1, -1]


class TestTokenLag:
    def test_one_lag_record_per_token_written_over_a_socket(self):
        from repro.launch.server import HttpFrontendServer, sse_client

        async def get(port, path):
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", port)
            writer.write(f"GET {path} HTTP/1.1\r\nHost: x\r\n\r\n"
                         .encode())
            await writer.drain()
            raw = await reader.read()
            writer.close()
            return json.loads(raw.split(b"\r\n\r\n", 1)[1])

        async def main():
            eng, _ = make_engine(tracer=traced())
            fe = AsyncFrontend(eng, idle_sleep_s=0.001)
            server = HttpFrontendServer(fe, "127.0.0.1", 0)
            await server.start()
            tokens = {}
            try:
                async def one(prompt, n):
                    got, rid = 0, None
                    async for ev, data in sse_client(
                            "127.0.0.1", server.port,
                            {"prompt": prompt, "max_new_tokens": n}):
                        if ev == "token":
                            got += 1
                        else:
                            rid = data["req_id"]
                    tokens[rid] = got
                await asyncio.gather(one([1, 2, 3, 4], 3),
                                     one([5, 6, 7], 5))
                stats = await get(server.port, "/metrics")
                trace = await get(server.port, "/trace")
            finally:
                await server.stop()
            tr = eng.tracer
            writes = [s for s in tr.spans if s.name == "server.write"]
            assert sorted(tokens.values()) == [3, 5]
            assert {rid: sum(1 for w in writes if w.req_id == rid)
                    for rid in tokens} == tokens
            commits = {s.end for s in tr.spans
                       if s.name == "scheduler.commit"}
            for w in writes:
                assert w.parent == -1
                assert w.attrs["committed"] in commits
                assert w.end >= w.attrs["committed"]
            assert stats["tokens_streamed"] == len(writes) == 8
            names = {r["name"] for r in trace}
            assert {"engine.step", "frontend.fanout",
                    "server.write"} <= names

        asyncio.run(main())

    def test_a_client_walking_away_leaves_no_write_span_open(
            self, annotations):
        from repro.launch.server import HttpFrontendServer, sse_client

        async def main():
            eng, _ = make_engine(tracer=traced())
            fe = AsyncFrontend(eng, idle_sleep_s=0.001)
            server = HttpFrontendServer(fe, "127.0.0.1", 0)
            await server.start()
            try:
                async for ev, data in sse_client(
                        "127.0.0.1", server.port,
                        {"prompt": [5, 6, 7, 8], "max_new_tokens": 200},
                        max_events=2):
                    pass
                for _ in range(500):           # bounded, event-driven
                    if not eng.scheduler.running \
                            and not eng.scheduler.waiting:
                        break
                    await asyncio.sleep(0.01)
                # the server saw the client go and cancelled the stream
                assert fe.stats()["client_cancelled"] == 1
                assert annotations.open == 0
            finally:
                await server.stop()
            writes = [s for s in eng.tracer.spans
                      if s.name == "server.write"]
            assert len(writes) >= 2
            assert annotations.names.count(PREFIX + "server.write") == \
                len(writes)
            assert all(w.end >= w.attrs["committed"] for w in writes)

        asyncio.run(main())
