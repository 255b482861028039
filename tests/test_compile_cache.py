"""The compile cache's one rule (nd/cache.py).

Where `JAX_COMPILATION_CACHE_DIR` is set JAX already reads it and no
code sets a directory; unset, the cache is ONE fixed directory inside
the checkout. Entry points turn it on; library code (server warmup,
multihost init) never re-points it. Every case that needs a fresh
process-wide JAX config runs in a subprocess — in-process the suite's
own cache (conftest) is already bound, and resetting JAX's cache object
is the private-API dance this rule replaced.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

from deeplearning4j_tpu.nd import cache as cache_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
V, D, MAXLEN, BL = 23, 16, 32, 4


def _child(code, *, env_cache=None, timeout=600):
    """Run `code` in a fresh interpreter from the checkout root; returns
    the JSON object it prints last."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_cache is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_cache)
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


_SPY = (
    "import json, jax\n"
    "sets = []\n"
    "real = jax.config.update\n"
    "def spy(name, val):\n"
    "    sets.append(name)\n"
    "    return real(name, val)\n"
    "jax.config.update = spy\n"
    "from deeplearning4j_tpu.nd import enable_compilation_cache\n")


class TestTheRule:
    def test_env_set_no_code_sets_a_directory(self, tmp_path):
        placed = tmp_path / "placed-from-outside"
        got = _child(
            _SPY
            + "d = enable_compilation_cache('tests-tag', 0.0)\n"
            "print(json.dumps({'returned': d, 'sets': sets, 'cfg': "
            "jax.config.jax_compilation_cache_dir}))\n",
            env_cache=placed)
        assert got["returned"] == got["cfg"] == str(placed)
        assert "jax_compilation_cache_dir" not in got["sets"]
        # thresholds are not directories: still set in code
        assert "jax_persistent_cache_min_compile_time_secs" in got["sets"]

    def test_env_unset_one_fixed_directory_in_the_checkout(self):
        got = _child(
            _SPY
            + "d = enable_compilation_cache()\n"
            "print(json.dumps({'returned': d, 'sets': sets}))\n")
        assert got["returned"] == os.path.join(ROOT, ".jax_cache")
        assert got["sets"].count("jax_compilation_cache_dir") == 1

    def test_subdir_is_a_namespace_under_the_same_root(self):
        got = _child(
            "import json\n"
            "from deeplearning4j_tpu.nd import enable_compilation_cache\n"
            "print(json.dumps(enable_compilation_cache('tests-abc')))\n")
        assert got == os.path.join(ROOT, ".jax_cache", "tests-abc")
        assert os.path.isdir(got)

    def test_first_placement_wins_later_calls_do_not_repoint(self):
        """An entry point placed the cache; a second caller (another
        entry point's helper, a library that used to re-point) changes
        thresholds at most."""
        got = _child(
            "import json\n"
            "from deeplearning4j_tpu.nd import enable_compilation_cache\n"
            "a = enable_compilation_cache('tests-first')\n"
            "b = enable_compilation_cache('elsewhere', 0.0)\n"
            "print(json.dumps([a, b]))\n")
        assert got[0] == got[1] == os.path.join(ROOT, ".jax_cache",
                                                "tests-first")

    def test_root_is_fixed_inside_the_checkout_and_git_ignored(self):
        assert str(cache_mod.CACHE_ROOT) == os.path.join(ROOT, ".jax_cache")
        with open(os.path.join(ROOT, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()

    def test_retired_seams_are_gone(self):
        with pytest.raises(ImportError):
            import deeplearning4j_tpu.nd.compile_cache  # noqa: F401
        import inspect

        from deeplearning4j_tpu.serving.replica import spawn_replica
        assert "compile_cache_dir" not in inspect.signature(
            spawn_replica).parameters
        assert "cache_dir" not in inspect.signature(
            cache_mod.enable_compilation_cache).parameters


class TestLibraryNeverRepoints:
    def test_server_warmup_leaves_the_cache_where_it_was(self):
        from deeplearning4j_tpu.serving import GenerationServer
        from deeplearning4j_tpu.zoo.transformer import TransformerLM
        before = (jax.config.jax_compilation_cache_dir,
                  jax.config.jax_persistent_cache_min_compile_time_secs)
        net = TransformerLM(vocab_size=V, d_model=D, n_layers=1,
                            n_heads=4, max_len=MAXLEN, seed=3).init()
        GenerationServer(net, n_slots=2, n_blocks=20,
                         block_len=BL).warmup(2)
        assert before == (
            jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs)

    def test_multihost_init_leaves_the_cache_where_it_was(self,
                                                          monkeypatch):
        from deeplearning4j_tpu.parallel import multihost
        before = jax.config.jax_compilation_cache_dir

        def boom(*a, **k):
            raise RuntimeError("stop before real distributed init")

        monkeypatch.setattr(multihost, "_raw_initialize", boom)
        monkeypatch.setattr(multihost, "_transient", lambda e: False)
        with pytest.raises(RuntimeError, match="stop before"):
            multihost.initialize_multihost("127.0.0.1:1", 1, 0,
                                           max_attempts=1)
        assert jax.config.jax_compilation_cache_dir == before


class TestColdVsWarm:
    def test_second_process_hits_what_the_first_compiled(self, tmp_path):
        """The fleet-swap / replica-2 / next-chip-call scenario, in
        FRESH processes (a successor starts with empty in-memory
        caches; the persistent cache is all that carries over): a cold
        child warms one server's program grid, an identical second
        child re-warms it. The second must HIT the cache — counted from
        JAX's own cache events, not inferred from a timing — and spend
        less time in XLA. Subprocess isolation is deliberate: an
        in-process `jax.clear_caches()` variant poisons every later
        test in the suite with mass recompiles."""
        child = (
            "import json, jax\n"
            "ev = {'requests': 0, 'hits': 0}\n"
            "def on(event, **kw):\n"
            "    if event.endswith('compile_requests_use_cache'):\n"
            "        ev['requests'] += 1\n"
            "    elif event.endswith('cache_hits'):\n"
            "        ev['hits'] += 1\n"
            "jax.monitoring.register_event_listener(on)\n"
            "from deeplearning4j_tpu.monitor import (JitCompileCollector,\n"
            "                                        MetricsRegistry)\n"
            "from deeplearning4j_tpu.nd import enable_compilation_cache\n"
            "from deeplearning4j_tpu.serving import GenerationServer\n"
            "from deeplearning4j_tpu.zoo.transformer import TransformerLM\n"
            "enable_compilation_cache(min_compile_time_secs=0.0)\n"
            "coll = JitCompileCollector(MetricsRegistry()).install()\n"
            f"net = TransformerLM(vocab_size={V}, d_model={D}, "
            f"n_layers=2, n_heads=4, max_len={MAXLEN}, seed=3).init()\n"
            f"GenerationServer(net, n_slots=4, n_blocks=48, "
            f"block_len={BL}, speculative=4).warmup(6, 4)\n"
            "print(json.dumps(dict(ev, seconds=coll.compile_seconds())))\n")
        cache = tmp_path / "xla-cache"
        cold = _child(child, env_cache=cache)
        assert cold["requests"] > 0 and cold["hits"] == 0, cold
        assert any(not f.endswith("-atime") for f in os.listdir(cache))
        warm = _child(child, env_cache=cache)
        assert warm["requests"] == cold["requests"], (cold, warm)
        assert warm["hits"] >= 0.9 * warm["requests"], (cold, warm)
        assert warm["seconds"] < 0.75 * cold["seconds"], (cold, warm)
