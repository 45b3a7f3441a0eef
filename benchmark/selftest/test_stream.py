"""The `lp_stream`: a walk made in blocks is the walk made whole, the
stream's batches are TSBS's loader's rows in the loader's order, and a
plan holds the one body it has made ahead however long it runs."""

import numpy as np
import pytest

from harness import traffic

from test_oracles import reference

LOAD = {"kind": "lp_stream", "batch_rows": 60, "warm": {"batches": 2},
        "loop": {"kind": "closed", "clients": 1}}


@pytest.mark.parametrize("blocks", [[50], [1] * 50, [3, 3, 44], [7, 43]])
def test_the_walk_in_blocks_is_the_walk_whole(blocks):
    mod, _ = reference("tsbs-devops-cpu-4000")
    a, b = np.random.default_rng(7), np.random.default_rng(7)
    whole = mod.walk(a, 50, 24)
    w = mod.Walk(b, 24)
    assert np.array_equal(np.concatenate([w.take(n) for n in blocks]), whole)
    # and the generator stands where the whole walk leaves it: the stored
    # cells draw their host keys next
    assert mod.hosts_keys(a, 24) == mod.hosts_keys(b, 24)


def lines_of(ref, hundredths, first_row=0):
    """The loader's rows, one at a time: time order, host-major."""
    flat = hundredths.reshape(-1, hundredths.shape[-1])
    for r, row in enumerate(flat, first_row):
        ts = (ref.start_s + r // ref.hosts * ref.interval_s) * 10**9
        fields = ",".join(
            f"{n}={ref._table[k].tobytes().decode()}"
            for n, k in zip(ref.field_names, row))
        yield ref.keys[r % ref.hosts] + f" {fields} {ts:019d}\n".encode()


@pytest.mark.parametrize("batch_rows", [60, 17, 24, 100])
def test_the_stream_is_the_loader_s_rows_in_the_loader_s_order(batch_rows):
    mod, cfg = reference("tsbs-devops-cpu-4000")
    ref = mod.Reference(cfg, 11, stored=False)
    assert ref.hundredths is None and ref.values is None and ref.rows == 0
    stream = ref.stream_requests(batch_rows)
    got = [next(stream) for _ in range(40)]
    assert [n for _, n in got] == [batch_rows] * 40
    ticks = -(-40 * batch_rows // ref.hosts)
    want = b"".join(lines_of(ref, ref.stream_walk().take(ticks)))
    sent = b"".join(body for body, _ in got)
    assert sent == want[:len(sent)] and len(sent) > 0.9 * len(want) - 10**4
    # every call begins the same stream
    assert next(ref.stream_requests(batch_rows))[0] == got[0][0]


def test_a_stored_reference_streams_its_rows_and_ends():
    mod, cfg = reference("tsbs-devops-cpu-4000", span_s=300)
    ref = mod.Reference(cfg, 11)
    got = list(ref.stream_requests(50))                # 720 rows: 14.4 batches
    assert [n for _, n in got] == [50] * 14 + [20]
    assert b"".join(b for b, _ in got) == b"".join(lines_of(ref,
                                                            ref.hundredths))


class Sent:
    def __init__(self):
        self.meanwhile = None

    def request(self, method, path, body):
        assert body                                    # made before sent
        if self.meanwhile is not None:
            self.meanwhile()
        return 204, b""

    def close(self):
        pass


def test_a_stream_plan_holds_one_body_however_long_it_runs(monkeypatch):
    mod, cfg = reference("tsbs-devops-cpu-4000")
    ref = mod.Reference(cfg, 3, stored=False)
    plan = traffic.build(LOAD, ref, 3, 51.0)
    assert [q.units for q in plan.warm_touch] == [60, 60]
    assert plan.warm_touch[0].body == plan.stream.made.body
    assert plan.requests == [] and len(plan.keep) == 0
    monkeypatch.setattr(traffic, "Client", lambda port: Sent())
    clock = iter(np.arange(0, 1e6, 0.001))
    monkeypatch.setattr(traffic.time, "perf_counter", lambda: next(clock))
    traffic.run(plan, 0, 2.001)                        # 2 ticks a request
    sent = len(plan.results)
    assert sent == len(plan.requests) >= 1000
    assert not plan.exhausted
    assert all(q.body is None and q.units == 60 and q.stmt == {"kind": "write"}
               for q in plan.requests)
    assert plan.stream.made.body and plan.stream.made not in plan.requests
    # the traced phase goes on with the same stream, at its next batch
    phase = traffic.rest(plan)
    nxt = plan.stream.made.body
    traffic.run(phase, 0, 0.01)
    assert phase.results and phase.requests[0].units == 60
    again = ref.stream_requests(60)
    for _ in range(sent):
        next(again)
    assert next(again)[0] == nxt


def test_a_request_that_never_reaches_the_client_costs_no_row():
    """tools/control.py's broken send skips `Client.request`, and with it
    `meanwhile`: the next request is made when it is asked for."""
    mod, cfg = reference("tsbs-devops-cpu-4000")
    s = traffic.build(LOAD, mod.Reference(cfg, 3, stored=False), 3, 1.0).stream
    first = [s.next().body for _ in range(10)]          # no make() between
    again = mod.Reference(cfg, 3, stored=False).stream_requests(60)
    assert first == [next(again)[0] for _ in range(10)]
