"""Real-width compiles of the main-path programs for one TPU v5e chip.

Nothing runs here: each program is lowered from shapes and compiled for a
described (not attached) v5e chip, whose compiler refuses what the chip
would refuse — a program that does not fit its HBM included.  Widths are
the deployments' (:mod:`repro.deploy`), at n_max ≥ 1M: DEEP's for every
main-path program, the 768-d text deployment's for the search, insert and
build programs.

The topology is described inside a fixture, never at import: only one
process may load the TPU compiler library, and every test worker imports
this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import deploy
from repro.core import distributed as dist
from repro.core import entrance as ent_mod
from repro.core import graph as graph_mod
from repro.core import pq as pq_mod

HBM_BUDGET = 14 * 2 ** 30         # of the 16 GiB a v5e chip holds
WAVE_SEARCH, WAVE_INSERT = 64, 16
ENT_MEMBERS = 10_000              # a 1% entrance of a 1M corpus
BUILD_BLOCK = 64                  # Engine.build's default block


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # the TPU compiler library would otherwise write its logs under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_MIN_LOG_LEVEL", "3")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:        # noqa: BLE001 — whatever stops it, skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep such compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _engine(dep):
    """``dep``'s engine, with a codec trained on a tiny CPU sample: the
    codec is a compile-time constant, its values do not change what is
    compiled."""
    from repro.core import Engine
    eng = Engine(dep.spec())
    key = jax.random.PRNGKey(0)
    vecs, _ = dep.corpus(key, 2048)
    eng.install_codec(pq_mod.train_pq(key, vecs, eng.spec.pq_m))
    return eng


@pytest.fixture(scope="module")
def engine():
    return _engine(deploy.DEEP96)


@pytest.fixture(scope="module")
def text_engine():
    return _engine(deploy.TEXT768)


def _programs(eng, one_chip):
    """name -> (jitted program, argument shapes, static keywords)."""
    spec = eng.spec

    def shape(s, dtype=None):
        if not isinstance(s, tuple):
            s, dtype = s.shape, s.dtype
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)

    st = jax.tree.map(lambda s: shape(s.shape[1:], s.dtype),
                      dist.state_shapes(eng, 1, spec.n_max))
    sym = shape(eng._sym)
    books = shape(eng.codec.codebooks)
    i32 = jnp.int32
    build_kw = dict(e_pos=64, beam_width=4, max_hops=128)
    link = jax.jit(ent_mod.link_members,
                   static_argnames=("c_max", "r_ent", "n_max"))
    return {
        "search_many": (eng.search_many, (
            st, shape((WAVE_SEARCH, spec.dim), jnp.float32)), {}),
        "insert_many": (eng.insert_many, (
            st, shape((WAVE_INSERT, spec.dim), jnp.float32)), {}),
        "link_members": (link, (
            shape((ENT_MEMBERS,), i32), st.codes, sym),
            dict(c_max=st.ent.c_max, r_ent=spec.r_ent,
                 n_max=spec.n_max)),
        "build_block": (graph_mod._build_block, (
            st.store, spec.lspec, shape((BUILD_BLOCK, spec.dim), jnp.float32),
            st.codes, sym, books, shape((4,), i32)),
            dict(alpha=1.0, **build_kw)),
        "refine_block": (graph_mod._refine_block, (
            st.store, spec.lspec, shape((BUILD_BLOCK,), i32), st.codes, books,
            shape((4,), i32)), dict(alpha=1.2, **build_kw)),
        "repair_block": (eng._repair_block, (
            st.store, st.codes, sym, st.tombstone, st.cache, st.ctr_maint,
            shape((), i32)), {}),
        "finalize_cycle": (eng._finalize_cycle, (
            st.store, st.tombstone, st.free_list, st.free_count,
            st.free_mask, st.cache, st.ctr_maint), {}),
    }


def _compiler(eng, one_chip):
    """name -> the program compiled for the described chip, once."""
    programs, done = _programs(eng, one_chip), {}

    def get(name):
        if name not in done:
            fn, args, kw = programs[name]
            done[name] = fn.lower(*args, **kw).compile()
        return done[name]
    return get


@pytest.fixture(scope="module")
def compiled(engine, one_chip, no_persistent_cache):
    return _compiler(engine, one_chip)


@pytest.fixture(scope="module")
def text_compiled(text_engine, one_chip, no_persistent_cache):
    return _compiler(text_engine, one_chip)


def _total_bytes(exe) -> int:
    mem = exe.memory_analysis()
    return (mem.argument_size_in_bytes + mem.temp_size_in_bytes +
            mem.output_size_in_bytes)


def _gathers_lut(exe, wave: int, pq_m: int) -> bool:
    """Whether a gather of ``exe`` reads an f32[wave, pq_m, 256] operand:
    the wave's ADC LUTs."""
    text = exe.as_text()
    shapes = dict(re.findall(r"%([\w.\-]+) = (\w+\[[\d,]*\])", text))
    operands = re.findall(r" gather\(%([\w.\-]+),", text)
    assert operands
    return f"f32[{wave},{pq_m},256]" in {shapes.get(op) for op in operands}


@pytest.mark.parametrize("name", [
    "search_many", "insert_many", "link_members", "build_block",
    "refine_block", "repair_block", "finalize_cycle"])
def test_compiles_within_hbm(name, compiled):
    total = _total_bytes(compiled(name))
    assert total < HBM_BUDGET, (name, total)


@pytest.mark.parametrize("name,wave", [("search_many", WAVE_SEARCH),
                                       ("insert_many", WAVE_INSERT)])
def test_adc_has_no_lut_gather(name, wave, engine, compiled):
    """On TPU the ADC is a one-hot select: no gather reads the wave's
    [wave, M, 256] LUTs (a per-element gather runs serially there).  The
    commit's unbatched [M, 256] rows of ``pq.sym_distance`` are not the
    ADC and may stay gathers."""
    assert not _gathers_lut(compiled(name), wave, engine.spec.pq_m), name


@pytest.mark.parametrize("name", [
    "search_many", "insert_many", "build_block", "refine_block"])
def test_text768_compiles_within_hbm(name, text_compiled):
    """768-d, r 48, pq_m 96 and the deployment's search pool, at n_max
    1,065,536: the index fits one chip whole."""
    total = _total_bytes(text_compiled(name))
    assert total < HBM_BUDGET, (name, total)


@pytest.mark.parametrize("name,wave", [("search_many", WAVE_SEARCH),
                                       ("insert_many", WAVE_INSERT)])
def test_text768_adc_has_no_lut_gather(name, wave, text_engine,
                                       text_compiled):
    assert not _gathers_lut(text_compiled(name), wave,
                            text_engine.spec.pq_m), name


def _replay_loop_copies(text: str, p_max: int) -> list[str]:
    """Copies of a ``[p_max]`` array inside the innermost loop of the
    cache-trace replay (scope ``navis.cache_replay``) of compiled HLO
    ``text``, and in every computation that loop calls."""
    comps, cur = {}, None
    for line in text.splitlines():
        m = re.match(r"^(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif cur is not None:
            cur.append(line)

    def called(name, seen):
        if name in comps and name not in seen:
            seen.add(name)
            for line in comps[name]:
                refs = re.findall(
                    r"(?:calls|body|condition|to_apply)=%([\w.\-]+)", line)
                for group in re.findall(r"branch_computations=\{([^}]*)\}",
                                        line):
                    refs += re.findall(r"%([\w.\-]+)", group)
                for ref in refs:
                    called(ref, seen)
        return seen

    bodies = [re.search(r"body=%([\w.\-]+)", line).group(1)
              for lines in comps.values() for line in lines
              if " while(" in line and "navis.cache_replay" in line]
    inner = [b for b in bodies if not any(" while(" in line
                                          for line in comps[b])]
    assert inner, bodies
    wide = re.compile(r"= \w+\[%d\]\S* copy(-start)?\(" % p_max)
    return [line.strip()[:120] for b in inner for c in called(b, set())
            for line in comps[c] if wide.search(line)]


@pytest.mark.parametrize("name", ["search_many", "insert_many"])
def test_cache_replay_loop_copies_no_page_table(name, engine, one_chip,
                                                compiled):
    """Each replayed access updates the ``[p_max]`` page tables in place:
    the v5e compile of the replay's loop copies none of them (a copy there
    is a pass over the table on every access)."""
    p_max = _programs(engine, one_chip)[name][1][0].cache.status.shape[0]
    copies = _replay_loop_copies(compiled(name).as_text(), p_max)
    assert not copies, copies
