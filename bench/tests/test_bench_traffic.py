import numpy as np
import pytest

from harness.files import ROOT
from harness.traffic import Mix, rng

M = {"shape": (4, 4), "row": np.array([0, 1, 2, 3, 1]), "col": np.array([1, 0, 3, 2, 2]),
     "val": np.ones(5, np.float32)}


def test_inputs_from_the_seed():
    mix = Mix("cg-direct", ROOT)
    a = mix.draw_inputs(M, 3, seed=2**35)
    b = mix.draw_inputs(M, 3, seed=2**35)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert len(a) == 3 and a[0].dtype == np.float32 and not np.array_equal(a[0], a[1])
    assert not np.array_equal(mix.draw_inputs(M, 1, seed=1)[0], a[0])
    assert Mix("pr-direct", ROOT).draw_inputs(M, 2, seed=9) == [None, None]


def test_a_mix_resolves_its_parts_by_name():
    mix = Mix("pr-direct", ROOT)
    assert mix.arrivals.input_count(mix.params, 10.0) == 1
    kw = mix.solver.program_kwargs(mix.request, None)
    assert kw == {"iters": 20, "tol": 0.0, "damping": 0.85, "device_loop": True}
    assert mix.solver.spmm_programs(mix.request) == {"jit_while": (20, 1)}
    cg = Mix("cg-direct", ROOT)
    assert cg.solver.program_kwargs(cg.request, "b")["b"] == "b"
    assert cg.solver.spmm_programs(cg.request) == {"jit_body": (1, 1)}


def test_an_unknown_part_is_an_error(tmp_path):
    (tmp_path / "bench" / "traffic").mkdir(parents=True)
    (tmp_path / "bench" / "traffic" / "x.json").write_text(
        '{"arrivals": "zipf", "request": {"solver": "cg", "input": "uniform"}}')
    with pytest.raises(FileNotFoundError):
        Mix("x", str(tmp_path))


def test_closed_loop_counts_whole_requests():
    closed = Mix("cg-direct", ROOT).arrivals
    calls = []
    out = closed.measure(lambda i: calls.append(i) or i, [10, 20, 30], 0.0, lambda name: _Null())
    # a window of 0 s still finishes the request in flight
    assert out["requests"] == 1 and out["answers"] == [(0, 0)] and out["failed"] == 0
    assert out["metrics"]["solve_s"] == pytest.approx(out["window_s"])


def test_streams_differ_by_tag_and_take_large_seeds():
    a = rng(2**40 + 3, 1).random(4)
    assert not np.array_equal(a, rng(2**40 + 3, 2).random(4))
    assert np.array_equal(a, rng(2**40 + 3, 1).random(4))


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False
