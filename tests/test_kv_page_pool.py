"""The KV page pool (``models/kv_page_pool.py``) against its plain twin, the
benchmark's ``numpy`` reference against the deployment's ``jax.numpy``
content, and the KV block stream's deployment
(``benchmark/deployments/kv_block_stream.py``) on the CPU's forced host
devices at a small size: a transfer against the reference, the guarantees
it prints, and the controls that have to come out not correct. The last
two tests compile the pool's programs at the cell's real size for a
described v5e chip: what the chip's compiler would refuse, it refuses here."""

import copy
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import generator, manifest  # noqa: E402
from test_stream_link_deployment import limited  # noqa: E402 — a test's own time limit

CONFIG = manifest.load_json("configs", "kv_block_stream_ici.json")
TRAFFIC = {
    "sizes": [16384], "message_bytes": 4096, "carrier": "attachment",
    "service": "StreamService", "method": "Open", "callers": 1,
    "warm_calls_per_caller": 1, "warm_seconds": 0.0,
}
REFERENCE = manifest.load_module("references", "kv_block_pool.py")


# -- the pool -----------------------------------------------------------------


def seeded_blocks(seed: int, k: int, words: int) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        0, 2**32, size=(k, words), dtype=np.uint32)


@pytest.mark.parametrize("as_sequence", [False, True], ids=["array", "sequence"])
@pytest.mark.parametrize("k", [1, 3, 4])
def test_write_equals_its_plain_twin(k, as_sequence):
    import jax
    import jax.numpy as jnp

    from incubator_brpc_tpu.models import kv_page_pool

    device = jax.devices()[1]
    kv = kv_page_pool.KvPagePool(16, 256)
    pool = kv.init_state(device)
    assert pool.devices() == {device} and pool.shape == (16, 256)
    assert not np.asarray(pool).any()
    plain = jnp.zeros((16, 256), jnp.uint32)
    rng = np.random.default_rng(k)
    before = kv_page_pool.m_pages_written.get_value()
    for round_ in range(5):
        blocks = seeded_blocks(10 * k + round_, k, 256)
        pages = rng.integers(0, 16, size=k).astype(np.int32)
        given = [jax.device_put(b, device) for b in blocks]
        given = tuple(given) if as_sequence else jnp.stack(given)
        old = pool
        pool = kv.write(pool, pages, given)
        plain = kv_page_pool.write_plain(plain, pages, jnp.asarray(blocks))
        assert old.is_deleted()  # donated: updated where it lies
        assert pool.devices() == {device}
    assert np.array_equal(np.asarray(pool), np.asarray(plain))
    assert kv_page_pool.m_pages_written.get_value() - before == 5 * k
    got = kv.read(pool, np.array([3, 0, 3], np.int32))
    assert got.devices() == {device}
    assert np.array_equal(np.asarray(got), np.asarray(plain)[[3, 0, 3]])
    assert not pool.is_deleted()  # a read donates nothing


def test_the_later_of_two_blocks_for_one_page_wins():
    import jax

    from incubator_brpc_tpu.models import kv_page_pool

    kv = kv_page_pool.KvPagePool(4, 64)
    pool = kv.init_state(jax.devices()[0])
    blocks = seeded_blocks(5, 3, 64)
    pool = kv.write(pool, np.array([2, 1, 2], np.int32), tuple(blocks))
    got = np.asarray(pool)
    assert np.array_equal(got[2], blocks[2]) and np.array_equal(got[1], blocks[1])
    assert not got[0].any() and not got[3].any()
    assert kv.nbytes == 4 * 64 * 4
    with pytest.raises(ValueError):
        kv_page_pool.KvPagePool(0, 64)


# -- the reference --------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 7, 2**40 + 3])
def test_the_references_content_equals_the_deployments_on_the_device(seed):
    """``numpy`` and ``jax.numpy`` compute a block's words alike, whatever
    the seed (the driver's are larger than 32 signed bits hold)."""
    module = manifest.load_module("deployments", "kv_block_stream.py")
    keys = REFERENCE.block_keys(seed, 2, 91, 16)
    assert keys.shape == (16, 2) and keys.dtype == np.uint32
    on_device = np.asarray(module.device_words(keys, 1024, REFERENCE.GOLDEN))
    for b in range(16):
        want = REFERENCE.content(seed, 2, 91, b, 1024)
        assert want.dtype == np.uint32 and np.array_equal(on_device[b], want)
    # no two blocks of a run carry the same words
    assert len({on_device[b].tobytes() for b in range(16)}) == 16
    other = REFERENCE.content(seed, 2, 92, 0, 1024)
    assert not np.array_equal(other, on_device[0])
    assert not np.array_equal(REFERENCE.content(seed + 1, 2, 91, 0, 1024), on_device[0])


def test_the_reference_places_blocks_round_robin_and_imports_nothing_of_the_program():
    pool = REFERENCE.Pool(8, 32, seed=3)
    assert pool.place(0, 0, 6, 4) == [6, 7, 0, 1]
    assert np.array_equal(pool.page(7), REFERENCE.content(3, 0, 0, 1, 32))
    assert not pool.page(5).any()  # never placed: as the pool was made
    assert pool.place(1, 4, 0, 2) == [0, 1]  # a later transfer takes the page
    assert np.array_equal(pool.page(0), REFERENCE.content(3, 1, 4, 0, 32))
    assert REFERENCE.expected(b"a", b"b") == (b"a", b"b")
    with open(os.path.join(manifest.HERE, "references", "kv_block_pool.py")) as f:
        imports = [line for line in f if line.startswith(("import ", "from "))]
    assert imports == ["import numpy as np\n"]


# -- the deployment -------------------------------------------------------------


def small_config() -> dict:
    """The configuration as its file states it, but for the sizes: 4 KiB
    slots, a link window of 4 and a 16 KiB stream window."""
    config = copy.deepcopy(CONFIG)
    config["channel_options"].update(link_slot_words=1024, link_window=4)
    config["stream"].update(max_buf_size=16384)
    return config


def deploy(control=None):
    module = manifest.load_module("deployments", "kv_block_stream.py")
    deployment = module.Deployment(small_config(), control, None)
    deployment.warm(TRAFFIC)
    return module, deployment


def payload(seed: int) -> bytes:
    return generator.make_pool({"sizes": [16384], "pool_per_size": 1}, seed, 0)[16384][0]


@limited(180)
def test_a_transfer_against_the_reference():
    import jax

    module, deployment = deploy()
    try:
        assert (deployment.blocks, deployment.block_bytes) == (4, 4096)
        assert deployment.kv.pages == CONFIG["rehearsal_pages"]
        send = generator.channel_caller(deployment.channel(), TRAFFIC, REFERENCE)
        statuses = [send(payload(seed))[1] for seed in (41, 42, 43)]
        assert statuses == [generator.OK] * 3
        # the pool on the decode device holds the three transfers' blocks
        # in the next twelve pages, in order
        prefill, decode = deployment.link.devices
        assert deployment.pool.devices() == {decode} and prefill != decode
        got = np.asarray(deployment.pool)
        for transfer in range(3):
            for b in range(4):
                want = REFERENCE.content(0, 0, transfer, b, 1024)
                assert np.array_equal(got[4 * transfer + b], want)
        assert not got[12:].any()
        # the next transfer's blocks wait on the prefill device
        (caller,) = deployment._callers
        assert caller.blocks_of == 3 and len(caller.blocks) == 4
        assert all(isinstance(b, jax.Array) and b.devices() == {prefill}
                   for b in caller.blocks)
        checks = {name.split("_of_")[0].split("_other_than")[0]: (value, ok)
                  for name, value, _limit, ok in deployment.holds()}
        assert all(ok for _value, ok in checks.values()), checks
        assert checks["link_geometry"][0] == "ppermute"
        assert checks["link_distinct_devices"][0] == 2
        for name in ("kv_blocks_not_equal_to_their_source",
                     "kv_pages_not_equal_on_the_host", "kv_lane_bytes",
                     "kv_messages_with_other_shapes", "stream_window_overrun_bytes",
                     "kv_messages_handed_as_host_bytes",
                     "kv_receipts_with_other_counts"):
            assert checks[name][0] == 0, name
    finally:
        deployment.close()


@pytest.mark.parametrize("control", ["flip_bit", "stale", "reorder", "host_bytes"])
@limited(180)
def test_a_control_comes_out_not_correct(control):
    module, deployment = deploy(control)
    assert control in module.CONTROLS
    try:
        send = generator.channel_caller(deployment.channel(), TRAFFIC, REFERENCE)
        statuses = [send(payload(seed))[1] for seed in (41, 42)]
        held = {name.split("_of_")[0].split("_other_than")[0]: ok
                for name, _v, _l, ok in deployment.holds()}
        if control == "host_bytes":
            # the blocks are right and where they belong; they crossed as
            # host bytes, which guarantee (4) forbids
            assert statuses == [generator.OK] * 2
            assert held["kv_messages_handed_as_host_bytes"] is False
            assert held["kv_blocks_not_equal_to_their_source"] is True
        else:
            # the first transfer has no earlier one to be stale with
            first = generator.OK if control == "stale" else generator.MISMATCH
            assert statuses == [first, generator.MISMATCH]
            assert held["kv_blocks_not_equal_to_their_source"] is False
            assert held["kv_pages_not_equal_on_the_host"] is False
            assert held["kv_messages_handed_as_host_bytes"] is True
        assert held["stream_window_overrun_bytes"] is True
    finally:
        deployment.close()


def test_a_program_without_the_lane_is_refused_before_a_stream_is_opened(monkeypatch):
    from incubator_brpc_tpu.transport import device_link

    module = manifest.load_module("deployments", "kv_block_stream.py")
    monkeypatch.delattr(device_link.DeviceLink, "lane_send")
    with pytest.raises(RuntimeError, match="no lane"):
        module.Deployment(small_config(), None, None)


# -- the pool's programs at the cell's size, for the chip's compiler -------------


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler for the chip here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def decode_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[1])


def real_pool(decode_chip):
    import jax
    import jax.numpy as jnp

    pages, words = CONFIG["pool_pages"], CONFIG["block_bytes"] // 4
    assert 4 * pages * words == 8 << 30
    return words, jax.ShapeDtypeStruct((pages, words), jnp.uint32, sharding=decode_chip)


def test_the_write_compiles_in_place_at_eight_gibibytes(decode_chip):
    import jax
    import jax.numpy as jnp

    from incubator_brpc_tpu.models.kv_page_pool import kv_page_write

    words, pool = real_pool(decode_chip)
    k = CONFIG["max_blocks_a_write"]
    pages = jax.ShapeDtypeStruct((k,), jnp.int32, sharding=decode_chip)
    blocks = tuple(jax.ShapeDtypeStruct((words,), jnp.uint32, sharding=decode_chip)
                   for _ in range(k))
    memory = jax.jit(kv_page_write, donate_argnums=0).lower(
        pool, pages, blocks).compile().memory_analysis()
    assert memory.alias_size_in_bytes == 8 << 30  # the pool, where it lies
    assert memory.temp_size_in_bytes < 64 << 20


def test_the_read_compiles_without_a_second_pool(decode_chip):
    import jax
    import jax.numpy as jnp

    from incubator_brpc_tpu.models.kv_page_pool import kv_page_read

    words, pool = real_pool(decode_chip)
    pages = jax.ShapeDtypeStruct((16,), jnp.int32, sharding=decode_chip)
    memory = jax.jit(kv_page_read).lower(pool, pages).compile().memory_analysis()
    assert memory.output_size_in_bytes == 16 * 4 * words
    assert memory.temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize(
    "shape,dtype",
    [((CONFIG["block_bytes"] // 4,), "uint32"), ((262144,), "uint32"),
     ((512, 8, 128, 2), "bfloat16")],
    ids=["a-2MiB-block", "a-1MiB-tensor", "a-2MiB-block-as-the-model-shapes-it"],
)
def test_the_lanes_program_compiles_for_two_chips_with_a_block_and_its_tag(
        topo, shape, dtype):
    """A 2 MiB block and its tag cross in one program: two collective
    permutes between the prefill and the decode chip, each an exchange (a
    message each way, or a placeholder back), and nothing of a block's
    size beside what lands. So does the 1 MiB tensor of a unary call
    (``link_performance_ici_hbm``), a request out and an answer back in
    the one program, and a block of more than one dimension."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from incubator_brpc_tpu.transport import device_link

    mesh = Mesh(np.asarray(topo.devices[:2]), ("link",))
    sharding = NamedSharding(mesh, P("link"))
    nbytes = int(np.prod(shape)) * jnp.dtype(dtype).itemsize
    halves = jax.ShapeDtypeStruct(
        (2 * shape[0],) + shape[1:], jnp.dtype(dtype), sharding=sharding)
    tags = jax.ShapeDtypeStruct(
        (2, device_link.LANE_TAG_WORDS), jnp.uint32, sharding=sharding)
    compiled = device_link.lane_program(mesh, sharding).lower(halves, tags).compile()
    text = compiled.as_text()
    assert "jit_device_link_lane" in text
    assert text.count("collective-permute-start(") == 2
    assert text.count("source_target_pairs={{0,1},{1,0}}") >= 2
    memory = compiled.memory_analysis()
    # the block and the tag's row, which the chip pads to a tile
    assert nbytes < memory.output_size_in_bytes <= nbytes + 4096
    assert memory.temp_size_in_bytes < 1 << 20


# -- the expert shard's step at the cell's size (PR 48; here because the chip's
# compiler is described once a process, by this file's fixture) ------------------


@pytest.mark.parametrize("batch", [1, 4, 16])
def test_the_expert_step_compiles_on_the_weights_where_they_lie(topo, batch):
    """``expert_shard_dsv3_ep32`` at its published widths: 8,455,716,864 B
    of weights are the program's arguments, its Pallas kernel takes them as
    they are, and beside them it holds the rows, the frames and the
    kernel's scratch: no copy of a layer (705 MB) or of an expert (88 MB),
    which is what a ``dynamic_slice`` of the stacked weights compiled to."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from incubator_brpc_tpu.models.expert_shard import ExpertShardService

    chip = SingleDeviceSharding(topo.devices[0])
    service = ExpertShardService(7168, 2048, 8, 12)
    service.interpret = False  # the kernel itself, for the chip's compiler

    def on_chip(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    state = (on_chip(12, 8, 7168, 2048), on_chip(12, 8, 7168, 2048),
             on_chip(12, 8, 2048, 7168))
    assert sum(2 * 12 * 8 * 7168 * 2048 for _ in state) == service.weight_bytes
    rows = on_chip(batch, 262144, dtype=jnp.uint32)
    ids = on_chip(batch, dtype=jnp.uint32)
    compiled = jax.jit(
        lambda s, r, c, m: service.step(s, r, c, m)[1]
    ).lower(state, rows, ids, ids).compile()
    assert "expert_ffn_grouped" in compiled.as_text()
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes >= service.weight_bytes
    # the frames, each padded to the chip's tile
    assert 0 <= memory.output_size_in_bytes - batch * 4 * (262144 + 8) <= batch * 512
    # a pass's gathered pairs, their float32 rows out and the float32 sum
    assert memory.temp_size_in_bytes < (64 << 20) * max(1, batch // 4)


def test_the_tensor_step_compiles_on_the_weights_where_they_lie(topo):
    """``expert_exchange_dsv3_ep32``'s rank step (PR 54) at the published
    widths and the operand's capacity of 2,048 rows: the weights are the
    program's arguments, the grouped kernel takes them as they are, the
    answer has the operand's shape, and a pass of the step holds under half
    a gigabyte beside them (the gathered pairs' rows, their float32 rows out,
    the float32 sum), whatever the split: a rank's chip stays under 1.25
    times its weights with two dispatches in flight."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from incubator_brpc_tpu.models.expert_shard import ExpertShardService

    chip = SingleDeviceSharding(topo.devices[1])
    service = ExpertShardService(7168, 2048, 8, 12)
    service.interpret = False  # the kernel itself, for the chip's compiler

    def on_chip(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    state = (on_chip(12, 8, 7168, 2048), on_chip(12, 8, 7168, 2048),
             on_chip(12, 8, 2048, 7168))
    operand = on_chip(2048, service.operand_words(), dtype=jnp.uint32)
    assert 4 * 2048 * service.operand_words() == 29_425_664
    compiled = jax.jit(service.dispatch_tensor).lower(
        state, on_chip(64, dtype=jnp.uint32), operand,
        on_chip(dtype=jnp.uint32), on_chip(dtype=jnp.uint32)).compile()
    assert "expert_ffn_grouped" in compiled.as_text()
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes >= service.weight_bytes
    assert 0 <= memory.output_size_in_bytes - 29_425_664 - 4 * 72 <= 4096
    assert memory.temp_size_in_bytes < 512 << 20


def test_the_sources_gather_and_combine_compile_without_an_axis_of_two(topo):
    """``expert_exchange_dsv3_ep32``'s source programs (PR 56) at the cell's
    shapes: the chip lays an operand ``uint32[2048, 3592]`` column-major
    (the layouts below are why the programs work on its transpose), neither
    program copies an operand or an answer to turn it, and all either holds
    beside its arguments and result is the ranks' gathered token rows (the
    parent's combine held 940 MB: three ``uint32[8192, 7168]`` through a
    trailing axis of 2, which the chip pads to 128 lanes)."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from incubator_brpc_tpu.models.expert_exchange import ExpertExchange

    chip = SingleDeviceSharding(topo.devices[0])
    tokens, hidden, capacity, held = 8192, 7168, 2048, 8

    def on_chip(*shape, dtype=jnp.uint32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    exchange = ExpertExchange(
        [None] * 3, [0, held, 2 * held], held, hidden, tokens, capacity, topo.devices[0])
    assert exchange.operand_shape == (2048, 3592)
    operand = on_chip(*exchange.operand_shape)
    gather = exchange._gather.lower(
        on_chip(tokens, hidden, dtype=jnp.bfloat16), on_chip(3, capacity, dtype=jnp.int32),
        on_chip(3, capacity, held, dtype=jnp.float32)).compile()
    combine = exchange._combine.lower(
        on_chip(3, capacity, dtype=jnp.int32), on_chip(3, tokens, dtype=jnp.int32),
        operand, operand, operand).compile()
    rows = 3 * capacity * hidden * 2  # the ranks' token rows in bf16, once
    for compiled in (gather, combine):
        text = compiled.as_text()
        entry = text[text.index("ENTRY"):]
        assert "tpu_custom_call" in entry
        assert re.search(r"u32\[2048,3592\]\{0,1:T\(8,128\)\}", entry)  # column-major
        assert not re.search(r"= u32\[(2048,3592|3592,2048)\]\S* (copy|transpose)\(", entry)
        assert not re.search(r"\[\d+,\d+,2\]", entry)  # no minor axis of 2
        assert compiled.memory_analysis().temp_size_in_bytes <= rows + (1 << 20)
    # the micro-batch fetched into VMEM ahead of XLA's row gather (136 us
    # there against 647 from HBM): what the pack's VMEM limit is set for
    assert re.search(r"copy-start\(%x[.\d]*\)", gather.as_text())
    assert gather.memory_analysis().output_size_in_bytes < 3 * 4 * 2048 * 3592 + (1 << 20)
    assert combine.memory_analysis().output_size_in_bytes == tokens * hidden * 2
    # a capacity the kernels' 128 rows do not divide is served on the chip too
    # (PR 55's hand-in raised ValueError there): the plain programs, no kernel
    odd = ExpertExchange(
        [None] * 3, [0, held, 2 * held], held, hidden, 1024, 1000, topo.devices[0])
    operand = on_chip(*odd.operand_shape)
    for compiled in (
            odd._gather.lower(
                on_chip(1024, hidden, dtype=jnp.bfloat16), on_chip(3, 1000, dtype=jnp.int32),
                on_chip(3, 1000, held, dtype=jnp.float32)).compile(),
            odd._combine.lower(
                on_chip(3, 1000, dtype=jnp.int32), on_chip(3, 1024, dtype=jnp.int32),
                operand, operand, operand).compile()):
        assert "tpu_custom_call" not in compiled.as_text()
