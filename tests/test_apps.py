"""Application-level tests: mandelbrot/PSIA through the robust queue with
real compute — the final artifact must be loss-less under failures."""

import contextlib

import jax
import numpy as np
import pytest

from repro.apps import mandelbrot, psia
from repro.core import dls, rdlb
from repro.kernels import ref

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
PX_SIDE, PX_ITERS = 64, 48          # 4,096 one-pixel tasks


@pytest.fixture(scope="module")
def px_image():
    """The plain jnp reference's escape counts, flat in pixel order."""
    cr, ci = mandelbrot.grid(PX_SIDE)
    img = np.asarray(jax.jit(ref.mandelbrot, static_argnums=2)(
        cr, ci, PX_ITERS))
    assert 0 < int((img == PX_ITERS).sum()) < img.size
    return img.reshape(-1)


@contextlib.contextmanager
def _compiles():
    """Counts the programs compiled inside the block."""
    seen = []

    def on(event, secs, **_):
        if event == BACKEND_COMPILE:
            seen.append(secs)
    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(on)


def test_mandelbrot_tiles_survive_failures():
    """Drop a 'worker's' in-flight tiles; rDLB re-issues; assembled image
    equals the directly computed one."""
    side, tile = 128, 32
    n = mandelbrot.n_tiles(side, tile)           # 16 tiles
    q = rdlb.RobustQueue(n, dls.make_technique("SS", n, 3))
    tiles = {}
    dead = {1}
    held = []
    while not q.done:
        progressed = False
        for pe in range(3):
            c = q.request(pe)
            if c is None:
                continue
            progressed = True
            if pe in dead:
                held.append(c)                    # never reports
                continue
            for t in c.tasks():
                if t not in tiles:
                    tiles[t] = mandelbrot.compute_tile(t, side=side,
                                                       tile=tile,
                                                       max_iters=64)
            q.report(c)
        if not progressed:
            break
    assert q.done
    img = mandelbrot.assemble(tiles, side=side, tile=tile)
    want = mandelbrot.escape_counts(side, 64)
    assert np.array_equal(img, want)


def test_psia_chunk_recompute_identical():
    """Re-executing a PSIA chunk yields identical spin images (the
    idempotence rDLB relies on)."""
    a = psia.compute_tasks([3, 5, 7], n=64, cloud_n=512)
    b = psia.compute_tasks([3, 5, 7], n=64, cloud_n=512)
    assert np.array_equal(a, b)
    assert a.shape == (3, psia.N_BETA, psia.N_ALPHA)


PSIA_N, PSIA_CLOUD = 64, 512


@pytest.mark.parametrize("ids", [
    [0], list(range(20, 27)), list(range(3, 16)), list(range(51, 64)),
    [3, 5, 7], [7, 3], [5, 5]],
    ids=["first", "size7", "across8", "last", "gaps", "unsorted",
         "repeated"])
def test_psia_chunks_match_the_gather(ids):
    """Each run of consecutive ids, sliced on the device from its start,
    gives the spin images of the kernel on the gathered rows, bit for
    bit and in the order of the ids."""
    from repro.kernels.ops import spin_image
    ctr, nrm = psia.oriented_points(PSIA_N)
    take = np.asarray(ids)
    want = jax.jit(lambda pts, c, n: spin_image(
        pts, c, n, n_alpha=psia.N_ALPHA, n_beta=psia.N_BETA,
        alpha_max=3.0, beta_max=3.0, block_p=psia.BLOCK_P))(
        psia.cloud(PSIA_CLOUD), ctr[take], nrm[take])
    got = psia.compute_tasks(ids, n=PSIA_N, cloud_n=PSIA_CLOUD)
    assert got.shape == (len(ids), psia.N_BETA, psia.N_ALPHA)
    np.testing.assert_array_equal(got, np.asarray(want))


def test_psia_chunk_opens_the_chunk_spans(monkeypatch):
    """A chunk is one run, so one pair of the chunk spans; ids with gaps
    open one pair per run."""
    from repro.core import trace as trc
    opened = []
    span = trc.span

    @contextlib.contextmanager
    def spy(name):
        opened.append(name)
        with span(name):
            yield
    monkeypatch.setattr(trc, "span", spy)
    psia.compute_tasks(np.arange(3, 10), n=PSIA_N, cloud_n=PSIA_CLOUD)
    assert opened == ["chunk.dispatch", "chunk.to_host"]
    opened.clear()
    psia.compute_tasks([3, 5, 7], n=PSIA_N, cloud_n=PSIA_CLOUD)
    assert opened == ["chunk.dispatch", "chunk.to_host"] * 3


def test_psia_chunk_start_is_traced():
    """Two chunks of one size at different starts compile one program."""
    jax.block_until_ready((psia.cloud(PSIA_CLOUD),
                           psia.oriented_points(PSIA_N)))
    with _compiles() as seen:
        for start in (5, 40):           # a size no other test compiles
            psia.compute_tasks(np.arange(start, start + 11), n=PSIA_N,
                               cloud_n=PSIA_CLOUD)
    assert len(seen) == 1


def test_mandelbrot_task_times_high_variance():
    tt = mandelbrot.task_times(1024, side=64, max_iters=128)
    assert tt.std() / tt.mean() > 0.5
    assert (tt > 0).all()


@pytest.mark.parametrize("start,size", [
    (0, 1), (63, 7), (60, 127), (128, 128), (1000, 129), (2000, 1025),
    (4095, 1), (3967, 129)])
def test_mandelbrot_pixel_chunks_match_reference(px_image, start, size):
    """One-pixel tasks: ranges that cross rows, sizes round the slab's
    128 lanes and 1,024 pixels, and the grid's last pixel."""
    got = mandelbrot.compute_tiles(start, start + size, side=PX_SIDE,
                                   tile=1, max_iters=PX_ITERS)
    assert got.shape == (size, 1, 1) and got.dtype == np.int32
    np.testing.assert_array_equal(got.reshape(-1),
                                  px_image[start:start + size])


def test_mandelbrot_pixel_fac_loop_through_chunk_backend(px_image):
    """A whole FAC loop of one-pixel tasks through the engine's
    ``ChunkBackend``, as the benchmark runs it: the image is exact."""
    from repro import api
    from repro.runtime import ChunkBackend
    n = PX_SIDE * PX_SIDE
    backend = ChunkBackend(
        lambda a, b: mandelbrot.compute_tiles(a, b, side=PX_SIDE, tile=1,
                                              max_iters=PX_ITERS), n)
    spec = api.RunSpec(
        scheduling=api.SchedulingSpec(technique="FAC"),
        robustness=api.RobustnessSpec(rdlb_enabled=True),
        cluster=api.ClusterSpec(n_workers=4),
        execution=api.ExecutionSpec(mode="threaded"), n_tasks=n)
    stats = api.run(spec, api.build(spec, backend))
    assert not stats.hung and stats.n_finished == n
    assert len({c.size for c in stats.assignment_log}) > 3
    np.testing.assert_array_equal(backend.results.reshape(-1), px_image)


@pytest.mark.parametrize("start,stop", [(0, 1), (1, 3), (3, 4), (0, 4)])
def test_mandelbrot_tile_chunks_match_compute_tile(start, stop):
    """64 x 64 tiles, the runtime's older unit, through the same slab."""
    side, tile, iters = 128, 64, 32
    img = mandelbrot.escape_counts(side, iters)
    got = mandelbrot.compute_tiles(start, stop, side=side, tile=tile,
                                   max_iters=iters)
    assert got.shape == (stop - start, tile, tile)
    for t, block in zip(range(start, stop), got):
        ty, tx = divmod(t, side // tile)
        np.testing.assert_array_equal(
            block, img[ty * tile:(ty + 1) * tile, tx * tile:(tx + 1) * tile])
        np.testing.assert_array_equal(
            block, mandelbrot.compute_tile(t, side=side, tile=tile,
                                           max_iters=iters))


def test_mandelbrot_chunks_up_to_a_slab_share_one_program():
    """Every chunk of at most 1,024 pixels, wherever it starts, runs one
    compiled program; a larger one compiles the next slab once."""
    side, iters = 40, 9                 # sizes no other test compiles
    kw = dict(side=side, tile=1, max_iters=iters)
    mandelbrot.compute_tiles(0, 1, **kw)     # the grid and the program
    with _compiles() as seen:
        for start, stop in [(5, 12), (39, 339), (1000, 1512), (0, 1024),
                            (1599, 1600)]:
            mandelbrot.compute_tiles(start, stop, **kw)
    assert seen == []
    with _compiles() as seen:
        mandelbrot.compute_tiles(0, 1025, **kw)
        mandelbrot.compute_tiles(500, 1600, **kw)
    assert len(seen) == 1


def test_mandelbrot_chunk_opens_the_chunk_spans(monkeypatch):
    """The chunk entry's host path is the two program spans of
    ``runtime.backends.chunk_to_host``."""
    from repro.core import trace as trc
    opened = []
    span = trc.span

    @contextlib.contextmanager
    def spy(name):
        opened.append(name)
        with span(name):
            yield
    monkeypatch.setattr(trc, "span", spy)
    mandelbrot.compute_tiles(7, 19, side=PX_SIDE, tile=1,
                             max_iters=PX_ITERS)
    assert opened == ["chunk.dispatch", "chunk.to_host"]
