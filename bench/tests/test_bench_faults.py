"""A whole run with the timed path broken underneath has to come out as not
correct: a step that returns its state unchanged, half of each batch left
out, an answer altered where it is produced, and (on four virtual
devices) the exchange between chips left out."""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import drive

BENCH = Path(__file__).resolve().parents[1]


def test_unchanged_state_fails():
    def plant(engine):
        engine._update = lambda state, src, dst, w: state
    assert drive.drive("rec-1m.churn", 101, plant=plant)["correct"] is False


def test_half_batch_fails():
    import jax.numpy as jnp

    def plant(engine):
        update = engine._update

        def half(state, src, dst, w):
            odd = jnp.arange(src.shape[0]) % 2 == 1
            return update(state, jnp.where(odd, -1, src), dst, w)
        engine._update = half
    assert drive.drive("rec-1m.steady", 102, plant=plant)["correct"] is False


def test_altered_answer_fails():
    def plant(engine):
        query = engine.query

        def altered(src, **kw):
            d, p, n = query(src, **kw)
            return d.at[0, 0].add(1), p, n
        engine.query = altered
    res = drive.drive("rec-1m.churn", 103, plant=plant)
    assert res["correct"] is False
    assert res["checks"]["read_diff"]["value"] > 0


def test_sound_run_passes():
    assert drive.drive("rec-1m.churn", 104)["correct"] is True


def test_missing_exchange_fails_on_four_devices(tmp_path):
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {str(BENCH / 'tests')!r})
        import drive
        import jax
        import run
        jax.lax.all_to_all = lambda x, *a, **k: x

        def four_shard_cell(name, scale_rows=0):
            cfg = run.load_json(run.BENCH / "configs" / "rec-8m-x4.json")
            cfg["mc"]["num_rows"] = scale_rows
            mix = run.load_json(run.BENCH / "traffic" / "read-x4.json")
            cell = {{"name": name, "config": "rec-8m-x4",
                     "traffic": "read-x4", "chips": 4}}
            return run.load_json(run.ROOT / "BENCHMARK.json"), cell, cfg, mix

        run.load_cell = four_shard_cell
        res = drive.drive("rec-8m-x4.read", 105)
        print("CORRECT", res["correct"])
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "CORRECT False" in out.stdout, out.stderr[-2000:]
