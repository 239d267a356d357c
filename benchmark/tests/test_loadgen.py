"""`loadgen.row_turns_per_s`: the throughput of a cell whose requests are
few, counted in row-turns between the first and the last completion.
Synthetic completions on a made-up clock: no scheduler, no jax."""
import pytest

from harness import loadgen

TURN_S = 0.254          # a guided turn of one long row, about


def _done(index, nfe, done_t, images=1):
    return loadgen.Done(index, {"nfe": nfe, "images": images}, object(),
                        None, done_t - 1.0, done_t - 1.0, done_t)


def _back_to_back(n, seed=7, start=0.0):
    """Requests served one after another in rounds of one row: each ends
    `nfe + 1` turns after the one before it."""
    out, clock = [], start
    for i, nfe in enumerate(loadgen.dealt_nfe(
            seed, {"2": 6, "3": 3, "4": 1}, n)):
        clock += (nfe + 1) * TURN_S
        out.append(_done(i, nfe, clock))
    return out


def _inside(done, t0, seconds):
    return [d for d in done if t0 <= d.done_t <= t0 + seconds]


def test_requests_served_back_to_back_read_the_turns_own_rate():
    done = _back_to_back(200)
    assert loadgen.row_turns_per_s(done) == pytest.approx(1 / TURN_S,
                                                          rel=1e-12)
    # whatever the order they were recorded in
    assert loadgen.row_turns_per_s(done[::-1]) == pytest.approx(
        1 / TURN_S, rel=1e-12)
    # and two images a request are two rows' turns
    twice = [_done(d.index, d.fields["nfe"], d.done_t, images=2)
             for d in done]
    assert loadgen.row_turns_per_s(twice) == pytest.approx(2 / TURN_S,
                                                           rel=1e-12)


def test_a_windows_edge_moves_the_count_of_images_and_not_the_turns_rate():
    """30 s hold 30 or 31 requests of 3 to 5 turns: `images / seconds`
    steps by a thirtieth as an edge passes a completion; the rate between
    completions stays where it is."""
    done = _back_to_back(400)
    by_images, by_turns = set(), []
    for k in range(60):                    # edges moved through 1.5 s: a
        t0 = 10.0 + 0.025 * k              # request is 0.76 to 1.27 s
        inside = _inside(done, t0, 30.0)
        by_images.add(round(len(inside) / 30.0, 9))
        by_turns.append(loadgen.row_turns_per_s(inside))
    assert len(by_images) >= 2
    assert max(by_images) - min(by_images) >= 1 / 30.0 - 1e-9
    assert max(by_turns) - min(by_turns) < 1e-9
    assert by_turns[0] == pytest.approx(1 / TURN_S, rel=1e-12)


@pytest.mark.parametrize("deal", [{"4": 1}, {"2": 6, "3": 3, "4": 1}],
                         ids=["every-row-alike", "the-dealt-mix"])
def test_pooled_completions_read_the_steady_rate_within_one_rounds_turns(
        deal):
    """Rounds of 8 rows, a turn of all 8 every `TURN_S`: a slot's next
    request starts where its last one ended, so rows that started
    together end together, 8 at one instant with rows alike. Each slot
    runs one turn a `TURN_S` all through; what the edges cut is under
    one request a slot, one round's turns in all."""
    rows, n = 8, 320
    nfes = loadgen.dealt_nfe(11, deal, n)
    done, clocks, nxt = [], [0.0] * rows, 0
    while nxt < n:
        slot = min(range(rows), key=lambda s: (clocks[s], s))
        clocks[slot] += (nfes[nxt] + 1) * TURN_S
        done.append(_done(nxt, nfes[nxt], clocks[slot]))
        nxt += 1
    inside = _inside(done, 5.0, 30.0)
    ts = sorted(d.done_t for d in inside)
    if len(deal) == 1:
        assert sum(1 for t in ts if t == ts[0]) == rows
    span = ts[-1] - ts[0]
    steady = rows / TURN_S
    longest = max(int(k) for k in deal) + 1
    got = loadgen.row_turns_per_s(inside)
    assert abs(got - steady) * span <= rows * longest
    assert got == pytest.approx(steady, rel=rows * longest / (steady * span))


@pytest.mark.parametrize("n", [0, 1, 2])
def test_fewer_than_three_completions_are_not_a_rate(n):
    with pytest.raises(ValueError, match="not a rate"):
        loadgen.row_turns_per_s(_back_to_back(n))


def test_completions_at_one_instant_are_not_a_rate():
    with pytest.raises(ValueError, match="not a rate"):
        loadgen.row_turns_per_s([_done(i, 2, 3.0) for i in range(4)])
