"""The flip_update work function, counted by hand at a real window."""
import importlib.util
from pathlib import Path


def _work():
    path = Path(__file__).resolve().parent / "work" / "flip_update.py"
    spec = importlib.util.spec_from_file_location("fu_work", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.work


def test_flip_update_work_at_sha2_window():
    # the sha2 5x5 window of the bring-up: K=4 candidate IIs, B=24 chains,
    # O=192 occurrence slots. Per chain: 192 clause ids (4 B) + 192 signs
    # (1 B) read, 192 true counts (4 B) read and written, 1 assignment
    # byte written = 192 * 13 + 1 = 2497 bytes; 96 chains.
    ops, nbytes = _work()(4, 24, 192)
    assert ops == 96 * 192 == 18432
    assert nbytes == 96 * 2497 == 239712


def test_flip_update_work_scales_with_each_dimension():
    work = _work()
    base = work(1, 32, 64)
    assert work(2, 32, 64) == (2 * base[0], 2 * base[1])
    assert work(1, 64, 64) == (2 * base[0], 2 * base[1])
    assert work(1, 32, 128)[0] == 2 * base[0]
