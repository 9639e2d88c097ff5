"""Compile-only checks against a described TPU v5e (no chip needed).

Each dispatch op compiles as `'auto'` resolves it on a TPU, at the
paper-scale chunk the facade's default `chunk_bytes` gives (2^23 f32
values per row, block 4096, a 12-book bank), for the chip's compiler.
An op whose TPU route is a Pallas kernel compiles with
``interpret=False``, so a kernel Mosaic refuses fails here, not on the
chip. Every compiled program must fit one v5e's 16 GB of HBM.

The topology is described inside a module fixture: only one process at
a time may load the TPU compiler's library, and the test workers import
every test file.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import dispatch
from repro.runtime import fused

V5E_HBM_BYTES = 16 * 10**9
BLOCK = 4096
BOOKS = 12                       # the shipped codebook bank's size
CHUNK = 1 << 23                  # CEAZConfig's default 32 MB f32 chunk
TABLE = 1 << 16                  # decode table rows per codebook
# chunk rows per field at the default chunk: HACC 2^23 values, NYX 256^3
ROWS = {"hacc": 1, "nyx": 2}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A sharding on one described chip, with JAX's persistent cache off
    (a program compiled for an absent chip cannot be read back)."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _op_call(op: str, rows: int):
    """(fn, arg dtypes/shapes) of one `op` call at `rows` x CHUNK values,
    with the shapes the fused runtime hands it."""
    nb = CHUNK // BLOCK
    w32 = fused.words_capacity(CHUNK)              # 16 bits/value worst case
    k_out = fused._k_outlier(CHUNK)
    impl = dispatch.auto_impl(op, "tpu")
    fn = dispatch.resolve(op, impl)
    kw = {"interpret": False} if impl == "pallas" else {}
    if op == "ceaz_chunk":
        bank_w32 = fused._bank_w32(fused.BANK_PROVISION_BITS, CHUNK)
        return (lambda *a: fn(*a, BLOCK, bank_w32, 33, "lorenzo", **kw),
                [((rows, CHUNK), jnp.float32), ((rows, 1), jnp.float32),
                 ((rows, CHUNK), jnp.bool_), ((rows,), jnp.float32),
                 ((BOOKS, 1024), jnp.int32), ((BOOKS, 1024), jnp.uint32)])
    if op == "hufenc":
        return (lambda *a: fn(*a, BLOCK, w32, 33, **kw),
                [((rows, CHUNK), jnp.int32), ((rows, CHUNK), jnp.bool_),
                 ((rows, 1024), jnp.int32), ((rows, 1024), jnp.uint32)])
    if op == "dq_center":
        return (lambda *a: fn(*a, **kw),
                [((rows, CHUNK), jnp.int32), ((rows, CHUNK), jnp.bool_)])
    stream = [((rows, w32), jnp.uint32), ((rows, nb), jnp.int32),
              ((rows,), jnp.int32), ((BOOKS * TABLE,), jnp.uint16),
              ((BOOKS * TABLE,), jnp.uint8), ((rows,), jnp.int32)]
    if op == "hufdec":
        return lambda *a: fn(*a, BLOCK, **kw), stream
    assert op == "ceaz_chunk_dec", op
    return (lambda *a: fn(*a, BLOCK, **kw),
            stream + [((rows, k_out), jnp.int32), ((rows,), jnp.int32),
                      ((rows,), jnp.int32), ((rows,), jnp.int32)])


def _compile(op: str, rows: int, sharding):
    fn, specs = _op_call(op, rows)
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in specs]
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("op,field", [
    ("ceaz_chunk", "hacc"), ("ceaz_chunk", "nyx"),
    ("hufenc", "hacc"), ("hufenc", "nyx"),
    ("ceaz_chunk_dec", "hacc"), ("hufdec", "hacc"), ("dq_center", "hacc"),
])
def test_tpu_auto_route_compiles_and_fits(op, field, one_chip):
    """What 'auto' runs on a TPU compiles for a v5e at the field's
    chunk rows and fits one chip's HBM; the program holds a Mosaic
    kernel exactly when the route is a Pallas kernel."""
    compiled = _compile(op, ROWS[field], one_chip)
    mem = compiled.memory_analysis()
    total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes)
    assert total < V5E_HBM_BYTES, (op, field, mem)
    has_kernel = "tpu_custom_call" in compiled.as_text()
    assert has_kernel == (dispatch.auto_impl(op, "tpu") == "pallas"), op


def test_tpu_hacc_bank_pass_fits(one_chip):
    """The whole single-pass bank encode of a HACC field (quantize ->
    select -> pack -> escapes, one traced program) at the default chunk
    compiles for a v5e and fits its HBM, with the literal-candidate
    capacity a field at rel 1e-4 gets (n // 16)."""
    n = CHUNK
    k_literal = fused.literal_capacity(n, 1e-4, 1.0)
    assert k_literal == n // 16
    w32 = fused._bank_w32(fused.BANK_PROVISION_BITS, n)
    lay = fused._BankLayout(
        dispatch.auto_impl("ceaz_chunk", "tpu"), "lorenzo", 1, (n,), n, 1,
        n, BLOCK, w32, w32, 33, fused._k_outlier(n), k_literal, True)
    assert lay.mega                 # the ceaz_chunk row-halo front end
    run = lay.pass_fn()
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    mem = run.lower(s((n,), jnp.float32), s((), jnp.float32),
                    s((BOOKS, 1024), jnp.int32),
                    s((BOOKS, 1024), jnp.uint32)).compile().memory_analysis()
    total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes)
    assert total < V5E_HBM_BYTES, mem
    # a paper-scale chunk should leave most of the chip free
    assert total < np.int64(V5E_HBM_BYTES) // 8, mem


def test_tpu_rank_sharded_gather_compiles_and_fits(topo, one_chip):
    """The rank-sharded bank pass over four 256x256x128 bricks, one per
    described chip of a v5e:2x2 (the NYX 16-rank deployment's host
    share), and the gather of its payloads compile and fit each chip;
    the pass holds no collective and the gather one collective, over one
    uint32 buffer per rank. At rel 1e-3 of a brick's range (1.0, with
    max |x| at most the range) the literal candidates keep the 1/256
    floor, so that buffer stays 4,262,918 words a rank."""
    import re
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core.codebook import default_codebook_bank
    from repro.io import collectives
    bank = default_codebook_bank()
    mesh = Mesh(np.array(topo.devices[:4]), ("r",))
    brick = (256, 256, 128)
    lay = fused._bank_layout(bank, brick, CHUNK, BLOCK,
                             dispatch.auto_impl("hufenc", "tpu"), "lorenzo",
                             True, ((1e-3, 1.0),) * 4)
    assert lay.k_literal == int(np.prod(brick)) // 256
    ranked = NamedSharding(mesh, P("r"))
    rep = NamedSharding(mesh, P())
    s = lambda shape, dt, sh: jax.ShapeDtypeStruct(shape, dt, sharding=sh)
    books = bank.lengths.shape
    args = (s((4,) + brick, jnp.float32, ranked), s((4,), jnp.float32, ranked),
            s(books, jnp.int32, rep), s(books, jnp.uint32, rep))
    pass_fn = fused._sharded_pass_fn(lay, mesh, "r")
    compiled = pass_fn.lower(*args).compile()
    coll = re.compile(r"= (\S+) (all-gather|all-reduce|reduce-scatter|"
                      r"collective-permute|all-to-all)(?:-start)?\(")
    assert coll.findall(compiled.as_text()) == []
    mem = compiled.memory_analysis()
    total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes)
    assert total < V5E_HBM_BYTES // 4, mem
    payload = [s(o.shape, o.dtype, ranked)
               for o in jax.eval_shape(pass_fn, *args)[:11]]
    n = sum(int(np.prod(p.shape)) // 4 for p in payload)
    assert n == 4_262_918
    gathered = collectives._gather_fn(mesh, "r", 11).lower(
        *payload).compile().as_text()
    # the TPU compiler may lower the all-gather as an all-reduce of each
    # rank's buffer placed in a zeroed (4, n) array: the same bytes
    (shape, op), = coll.findall(gathered)
    dims = [int(d) for d in re.match(r"u32\[([\d,]+)\]", shape)
            .group(1).split(",")]
    assert op in ("all-gather", "all-reduce"), op
    assert int(np.prod(dims)) == 4 * n, (shape, n)
