"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Nothing runs: each test lowers one kernel entry point at the paper's sizes
(2^26 elements per call) or a model's published widths, with the config
the tuning session resolves, and compiles it with the TPU compiler for a
``v5e:2x2`` topology described here without a chip.  What Mosaic refuses
(an unaligned block, more scoped VMEM than the limit the kernels compile
under, an op it cannot lower) fails here at no chip time.  Code that asks
``jax.default_backend()`` still sees the CPU, so the tests ask for the
compiled Pallas path explicitly (``use_pallas=True, interpret=False``).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

TOTAL = 2 ** 26


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler here: nothing to compile for
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def session_without_db(tmp_path_factory):
    """Configs come from the analytical model, as on a fresh checkout."""
    from repro.tuning import TunerSession, set_default_session
    path = tmp_path_factory.mktemp("tpu_compile") / "absent_db.json"
    previous = set_default_session(TunerSession(db_path=str(path)))
    yield
    set_default_session(previous)


@pytest.fixture
def compile_for(one_chip):
    """Compile ``fn`` for shapes on the described chip, with JAX's
    persistent cache off (a described-chip compile cannot be read back)."""
    from jax.experimental.compilation_cache import compilation_cache

    def run(fn, *shapes):
        args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
                for shape, dtype in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield run
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


F32 = jnp.float32
COMPILED = dict(use_pallas=True, interpret=False)


@pytest.mark.parametrize("variant", ["ks", "lf"])
@pytest.mark.parametrize("n", [1024, 4096])
def test_prefix_sum_compiles(compile_for, variant, n):
    from repro.kernels.scan.ops import prefix_sum
    text = compile_for(lambda x: prefix_sum(x, variant=variant, **COMPILED),
                       ((TOTAL // n, n), F32))
    assert "tpu_custom_call" in text


MULTIPASS = {"tile_n": 128, "rows_per_program": 8, "radix": 4}


def _kernel_names(text):
    """Base HLO instruction names of the Pallas kernels in ``text``."""
    return sorted(line.split(" = ", 1)[0].split()[-1].lstrip("%")
                  .split(".", 1)[0]
                  for line in text.splitlines()
                  if 'custom_call_target="tpu_custom_call"' in line)


@pytest.mark.parametrize("variant,shape,config,want", [
    ("ks", (4096, 1024), None, ["scan_ks"]),
    ("lf", (4096, 1024), None, ["scan_lf"]),
    ("linrec", (4096, 1024), None, ["scan_linrec"]),
    ("ks", (8, 16384), dict(MULTIPASS, unroll=2),
     ["scan_ks_apply", "scan_ks_carry", "scan_ks_chunk"]),
    ("linrec", (8, 16384), MULTIPASS,
     ["scan_linrec_apply", "scan_linrec_carry", "scan_linrec_chunk"]),
])
def test_scan_kernels_named_by_variant(compile_for, variant, shape, config,
                                       want):
    """A device trace tells the scan kernels apart by their HLO
    instruction names: the variant, and a stage suffix when multi-pass."""
    from repro.kernels.scan.ops import linear_recurrence, prefix_sum
    if variant == "linrec":
        text = compile_for(lambda a, b: linear_recurrence(
            a, b, config=config, **COMPILED), (shape, F32), (shape, F32))
    else:
        text = compile_for(lambda x: prefix_sum(
            x, variant=variant, config=config, **COMPILED), (shape, F32))
    assert _kernel_names(text) == want


def test_linear_recurrence_compiles(compile_for):
    from repro.kernels.scan.ops import linear_recurrence
    shape = ((TOTAL // 1024, 1024), F32)
    text = compile_for(lambda a, b: linear_recurrence(a, b, **COMPILED),
                       shape, shape)
    assert "tpu_custom_call" in text


def test_rglru_compiles(compile_for):
    from repro.kernels.rglru.ops import rglru
    shape = ((4, 2048, 2560), F32)          # recurrentgemma-9b lru width
    text = compile_for(lambda a, u: rglru(a, u, **COMPILED), shape, shape)
    assert "tpu_custom_call" in text


def test_tridiag_pcr_compiles(compile_for):
    from repro.kernels.tridiag.ops import solve
    shape = ((TOTAL // 64, 64), F32)
    text = compile_for(
        lambda a, b, c, d: solve(a, b, c, d, variant="pcr", interpret=False),
        shape, shape, shape, shape)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n", [4096, 8388608])
def test_fft_compiles(compile_for, n):
    """n = 4096 runs one resident kernel; 2^23 the four-step path."""
    from repro.kernels.fft.ops import fft
    text = compile_for(lambda x: fft(x, interpret=False),
                       ((TOTAL // n, n), jnp.complex64))
    assert "tpu_custom_call" in text


def _ops_over(text, shape):
    """Sorted opcodes (a custom call by its target) of the entry
    computation's instructions whose result has ``shape``."""
    import re
    entry = text[text.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    dims = "[" + ",".join(str(d) for d in shape) + "]"
    out = []
    for line in entry.splitlines()[2:]:
        m = re.match(r"(\(.*?\)|\S+) ([\w\-]+)\(", line.partition(" = ")[2])
        if m and dims in m.group(1) and m.group(2) not in (
                "parameter", "get-tuple-element"):
            target = re.search(r'custom_call_target="([^"]+)"', line)
            out.append(target.group(1) if target else m.group(2))
    return sorted(out)


GLUE = ["X64Combine", "X64SplitHigh", "X64SplitLow", "tpu_custom_call"]


@pytest.mark.parametrize("n,want", [
    # n = 64: the c64 input and the result are relaid out, whatever the
    # launch does
    (64, ["copy"] + GLUE[:3] + ["copy"] + GLUE[3:]),
    (128, GLUE), (256, GLUE), (512, GLUE), (1024, GLUE), (2048, GLUE),
    (4096, GLUE),
])
def test_fft_glue_inventory(compile_for, n, want):
    """At the ops.fft_pcr sizes the launch writes natural order: around
    it only the complex split and combine touch a (batch, n) array, with
    no copy, transpose or fusion that would reorder it in XLA."""
    from repro.kernels.fft.ops import fft
    shape = (TOTAL // n, n)
    text = compile_for(lambda x: fft(x, interpret=False),
                       (shape, jnp.complex64))
    assert _ops_over(text, shape) == sorted(want)


def test_ssd_compiles(compile_for):
    from repro.kernels.ssd.ops import ssd
    B, L, H, P, S = 4, 2048, 24, 64, 128    # mamba2-130m widths
    text = compile_for(lambda x, a, b, c: ssd(x, a, b, c, **COMPILED),
                       ((B, L, H, P), F32), ((B, L, H), F32),
                       ((B, L, S), F32), ((B, L, S), F32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("L,want", [
    (8192, ["ssd_carry", "ssd_chunk"]),
    (256, ["ssd_chunk"]),
])
def test_ssd_kernels_named(compile_for, L, want):
    """A device trace tells the SSD launches apart by their HLO
    instruction names: ``ssd_chunk`` (intra-chunk) and ``ssd_carry`` (the
    inter-chunk state-and-apply launch, absent with a single chunk);
    granite-4.0-h-micro's widths at chunk 256."""
    from repro.kernels.ssd.ops import ssd
    B, H, P, S = 1, 64, 64, 128
    config = {"tile_n": 256}
    text = compile_for(
        lambda x, a, b, c: ssd(x, a, b, c, config=config, **COMPILED),
        ((B, L, H, P), F32), ((B, L, H), F32), ((B, L, S), F32),
        ((B, L, S), F32))
    assert _kernel_names(text) == want


def test_flash_attention_compiles(compile_for):
    from repro.kernels.attention.ops import attention
    shape = ((64, 2048, 64), jnp.bfloat16)
    text = compile_for(lambda q, k, v: attention(q, k, v, **COMPILED),
                       shape, shape, shape)
    assert "tpu_custom_call" in text
