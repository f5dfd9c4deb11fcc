"""The traffic generator on a fake system: every mix shape a cell's file can
ask for, though the first three cells use one client in a closed loop."""

import threading
import time

import traffic


def _fake(served, cost_s=0.002):
    lock = threading.Lock()

    def issue(query, due_at):
        time.sleep(cost_s)
        with lock:
            served.append((query, due_at, time.monotonic()))
    return issue


def test_closed_loop_one_client_closes_after_the_last_answer():
    served = []
    elapsed = traffic.run_window(
        {"loop": "closed", "clients": 1, "queries": ["a"]}, 7, 0.05,
        _fake(served))
    assert elapsed >= 0.05 and len(served) >= 5
    assert all(due is None for _, due, _ in served)


def test_closed_loop_clients_share_one_seeded_order():
    mix = {"loop": "closed", "clients": 3, "queries": ["a", "b", "c"]}
    served = []
    traffic.run_window(mix, 11, 0.05, _fake(served))
    counts = {q: sum(1 for s in served if s[0] == q) for q in "abc"}
    assert max(counts.values()) - min(counts.values()) <= 3
    first = [q for q, _ in zip(traffic.query_order(mix["queries"], 11), range(6))]
    again = [q for q, _ in zip(traffic.query_order(mix["queries"], 11), range(6))]
    other = [q for q, _ in zip(traffic.query_order(mix["queries"], 12), range(60))]
    assert first == again and sorted(first) == sorted("abcabc")
    assert other != [q for q, _ in zip(
        traffic.query_order(mix["queries"], 11), range(60))]


def test_open_loop_sends_on_schedule():
    mix = {"loop": "open", "clients": 4, "queries": ["a"],
           "rate_per_s": 200.0, "arrivals": "poisson"}
    due = traffic.due_times(mix, 5, 0.1)
    assert due == traffic.due_times(mix, 5, 0.1) != traffic.due_times(mix, 6, 0.1)
    assert 5 <= len(due) <= 60 and all(0 <= d < 0.1 for d in due)
    served = []
    traffic.run_window(mix, 5, 0.1, _fake(served))
    assert len(served) == len(due)
    assert all(done >= at for _, at, done in served)


def test_between_runs_on_the_first_client():
    calls = []
    traffic.run_window({"loop": "closed", "clients": 2, "queries": ["a"]}, 1,
                       0.03, _fake([]), between=lambda: calls.append(1))
    assert calls
