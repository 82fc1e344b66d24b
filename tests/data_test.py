"""Data layer tests: TFRecord codec (with tf.train.Example as oracle when
available), windowed pipelines, interleave determinism + resume, mixture
weighting, run-log replay parity against actual consumption, video decode,
host->device feeding."""
import numpy as np
import pytest

from homebrewnlp_tpu.data import (GptPipeline, MixturePipeline, RecordWriter,
                                  count_records, decode_example,
                                  encode_example, read_records,
                                  skips_for_restart, synthetic_text_batch,
                                  to_global, write_text_tfrecords)
from homebrewnlp_tpu.data.pipeline import _FileWindows, _Interleave
from homebrewnlp_tpu.data.resume import RunLog, simulate_consumption

from .backend import mixer_config


def test_example_roundtrip():
    ex = {"text": b"hello world", "ids": [1, 5, 70000, 0], "w": [0.5, -1.25]}
    decoded = decode_example(encode_example(ex))
    assert decoded["text"] == [b"hello world"]
    assert decoded["ids"] == [1, 5, 70000, 0]
    assert decoded["w"] == [0.5, -1.25]


def test_example_matches_tensorflow_oracle():
    tf = pytest.importorskip("tensorflow")
    ours = encode_example({"text": b"abc", "ids": [3, 9, 127, 128, 300]})
    theirs = decode_example(
        tf.train.Example(features=tf.train.Features(feature={
            "text": tf.train.Feature(bytes_list=tf.train.BytesList(value=[b"abc"])),
            "ids": tf.train.Feature(int64_list=tf.train.Int64List(value=[3, 9, 127, 128, 300])),
        })).SerializeToString())
    assert theirs["text"] == [b"abc"] and theirs["ids"] == [3, 9, 127, 128, 300]
    # and tf can parse ours
    parsed = tf.io.parse_single_example(ours, {
        "text": tf.io.FixedLenFeature([], tf.string),
        "ids": tf.io.VarLenFeature(tf.int64)})
    assert parsed["text"].numpy() == b"abc"
    assert list(tf.sparse.to_dense(parsed["ids"]).numpy()) == [3, 9, 127, 128, 300]


def test_record_framing_roundtrip(tmp_path):
    p = str(tmp_path / "x.tfrecord")
    payloads = [b"a" * 3, b"b" * 1000, b""]
    with RecordWriter(p) as w:
        for x in payloads:
            w.write(x)
    assert list(read_records(p, verify=True)) == payloads
    assert count_records(p) == 3
    assert list(read_records(p, skip=2)) == [b""]


def test_tfrecord_readable_by_tensorflow(tmp_path):
    tf = pytest.importorskip("tensorflow")
    p = str(tmp_path / "x.tfrecord")
    with RecordWriter(p) as w:
        w.write(b"payload-1")
        w.write(b"payload-2")
    got = [r.numpy() for r in tf.data.TFRecordDataset(p)]
    assert got == [b"payload-1", b"payload-2"]


def test_file_windows_per_record(tmp_path):
    (path,) = write_text_tfrecords(str(tmp_path), 1, records_per_file=2,
                                   tokens_per_record=25, seed=1)
    # window 10+1, shift 10 -> per 25-token record: starts 0,10 => 2 windows
    wins = list(_FileWindows(path, window=11, shift=10))
    assert len(wins) == 4
    assert all(len(w) == 11 for w in wins)
    # consecutive windows overlap by 1 token (x/y offset)
    assert wins[0][10] == wins[1][0]


def test_gpt_pipeline_shapes_and_xy_offset(tmp_path):
    cfg = mixer_config(sequence_length=16)
    paths = write_text_tfrecords(str(tmp_path), 4, 4, 70, seed=3)
    pipe = GptPipeline(cfg, sub_batch_size=2, paths=paths)
    batch = next(iter(pipe))
    assert batch["token_x"].shape == (2, 16, 1)
    assert batch["token_y"].shape == (2, 16, 1)
    np.testing.assert_array_equal(batch["token_x"][:, 1:], batch["token_y"][:, :-1])


def test_interleave_deterministic_and_resumable(tmp_path):
    paths = write_text_tfrecords(str(tmp_path), 6, 3, 40, seed=5)
    def make():
        return _Interleave(sorted(paths), [0] * 6, window=17, shift=16,
                           cycle=3, repeat=False)
    full = [w.tobytes() for w in make()]
    assert len(full) > 10
    # same stream twice
    assert [w.tobytes() for w in make()] == full
    # stop after k, save state, resume
    k = 7
    inter = make()
    it = iter(inter)
    got = [next(it).tobytes() for _ in range(k)]
    state = inter.state_dict()
    resumed = make()
    resumed.load_state_dict(state)
    got += [w.tobytes() for w in resumed]
    assert got == full


def test_shuffled_pipeline_resume(tmp_path):
    """Resume with shuffling must reproduce the exact continuation (buffer
    contents rebuilt by replay)."""
    cfg = mixer_config(sequence_length=16, use_random_dataloader=True,
                       shuffle_buffer=8, interleaved_datasets=2)
    paths = write_text_tfrecords(str(tmp_path), 3, 2, 100, seed=13)

    def make():
        return GptPipeline(cfg, sub_batch_size=2, paths=paths)

    it = iter(make_pipe := make())
    consumed = [next(it) for _ in range(4)]
    state = make_pipe.state_dict()
    expected = [next(it)["token_x"].tobytes() for _ in range(3)]
    fresh = make()
    fresh.load_state_dict(state)
    got = []
    it2 = iter(fresh)
    got = [next(it2)["token_x"].tobytes() for _ in range(3)]
    assert got == expected
    assert consumed


def test_mixture_continues_after_child_exhausts():
    a = [{"x": np.full(1, 0)}] * 5
    b = [{"x": np.full(1, 1)}] * 50
    out = [int(m["x"][0]) for m in MixturePipeline([a, b], [1, 1], seed=3)]
    # all 55 elements are yielded; the mixture doesn't stop when `a` drains
    assert len(out) == 55
    assert out.count(0) == 5 and out.count(1) == 50


def test_mixture_weights_and_determinism():
    a = [{"x": np.full(1, 0)}] * 300
    b = [{"x": np.full(1, 1)}] * 300
    mix1 = list(MixturePipeline([a, b], [3, 1], seed=7))
    mix2 = list(MixturePipeline([a, b], [3, 1], seed=7))
    assert [m["x"][0] for m in mix1] == [m["x"][0] for m in mix2]
    frac = np.mean([m["x"][0] for m in mix1][:200])
    assert 0.1 < frac < 0.4  # ~0.25


def test_runlog_replay_matches_actual_consumption(tmp_path):
    """Property test (SURVEY.md §7 hard part): replay arithmetic must equal
    real pipeline consumption for a single-record-per-file dataset."""
    cfg = mixer_config(sequence_length=16, interleaved_datasets=2)
    paths = write_text_tfrecords(str(tmp_path), 5, 1, 130, seed=9)
    pipe = GptPipeline(cfg, sub_batch_size=2, paths=paths)
    # consume 3 batches = 6 windows
    it = iter(pipe)
    consumed_windows = [next(it) for _ in range(3)]
    log = RunLog(str(tmp_path))
    log.append(steps=3, batch_size=2, slice_count=1, ctx=16,
               interleave_size=2, token_patch_size=1)

    # actual continuation from the live iterator
    rest_actual = [b["token_x"].tobytes() for b in it]
    # continuation reconstructed purely from the run log
    pipe_replay = GptPipeline(cfg, sub_batch_size=2, paths=paths,
                              runs_log=log.runs)
    rest_replay = [b["token_x"].tobytes() for b in pipe_replay]
    assert rest_replay == rest_actual
    assert consumed_windows  # silence unused warning; 3 batches were drawn


def test_simulate_consumption_full_depletion():
    # 2 files, 100 tokens each, ctx 10 + patch 1 -> 9 windows per file
    depleted, consumed = simulate_consumption(
        [100, 100], [dict(steps=18, batch_size=1, slice_count=1, ctx=10,
                          grad_accumulation=1, interleave_size=2,
                          token_patch_size=1)])
    assert depleted == [True, True]
    assert consumed == [90, 90]


def test_to_global_feeds_mesh(eight_devices):
    import jax
    from homebrewnlp_tpu.parallel import make_mesh
    cfg = mixer_config(train_batch_size=8)
    mesh = make_mesh(cfg)
    batch = synthetic_text_batch(cfg)
    global_batch = to_global(batch, cfg, mesh)
    x = global_batch["token_x"]
    assert x.x.shape == (8, 16, 1)
    assert len(x.x.addressable_shards) == 8
    np.testing.assert_array_equal(np.asarray(x.x), batch["token_x"])


def test_video_pipeline(tmp_path):
    cv2 = pytest.importorskip("cv2")
    from homebrewnlp_tpu.data import write_video_tfrecords
    from homebrewnlp_tpu.data.video import VideoPipeline
    cfg = mixer_config(model_mode="jannet", use_video=True, use_language=False,
                       frame_height=32, frame_width=32, patch_size=16,
                       sequence_length=4, experts=1)
    paths = write_video_tfrecords(str(tmp_path), 2, 12, cfg, seed=11)
    pipe = VideoPipeline(cfg, sub_batch_size=2, paths=paths)
    batch = next(iter(pipe))
    # 3 axes: [B, t+1, hp, wp, color*patch^2]
    assert batch["frame"].shape == (2, 5, 2, 2, 16 * 16 * 3)
    assert batch["vid_msk_src"].shape == (2, 4)
    assert batch["cat_mask_x"].dtype == bool
    # first frame of each file is concat -> mask False somewhere
    assert not batch["cat_mask_x"].all() or not batch["cat_mask_y"].all()


def test_video_pipeline_exact_resume(tmp_path):
    """Resume mid-file reproduces the uninterrupted stream (window-level
    cursor, round-1 only kept the file index)."""
    cv2 = pytest.importorskip("cv2")
    from homebrewnlp_tpu.data import write_video_tfrecords
    from homebrewnlp_tpu.data.video import VideoPipeline
    cfg = mixer_config(model_mode="jannet", use_video=True, use_language=False,
                       frame_height=32, frame_width=32, patch_size=16,
                       sequence_length=4, experts=1)
    paths = write_video_tfrecords(str(tmp_path), 2, 30, cfg, seed=3)

    pipe = VideoPipeline(cfg, sub_batch_size=2, paths=paths)
    it = iter(pipe)
    batches = [next(it) for _ in range(5)]
    state = pipe.state_dict()
    assert state["windows_done"] > 0 or state["file_idx"] > 0
    expected = [next(it) for _ in range(3)]

    pipe2 = VideoPipeline(cfg, sub_batch_size=2, paths=paths)
    pipe2.load_state_dict(state)
    it2 = iter(pipe2)
    for want in expected:
        got = next(it2)
        for k in want:
            np.testing.assert_array_equal(got[k], want[k], err_msg=k)


def _write_video_shard_with_bad_frame(tmp_path, cfg, n_frames, bad_index):
    """One video shard where frame ``bad_index`` carries undecodable JPEG
    bytes (valid Example framing, garbage payload)."""
    import cv2
    rng = np.random.default_rng(5)
    path = str(tmp_path / "video0000.tfrecord")
    with RecordWriter(path) as w:
        for j in range(n_frames):
            if j == bad_index:
                frame_bytes = b"\xff\xd8 definitely not a jpeg"
            else:
                img = rng.integers(0, 256, (cfg.frame_height, cfg.frame_width,
                                            cfg.color_channels), np.uint8)
                ok, enc = cv2.imencode(".jpg", img)
                assert ok
                frame_bytes = enc.tobytes()
            w.write(encode_example({"frame": frame_bytes,
                                    "concat": [int(j == 0)],
                                    "skip_frame": [0]}))
    return [path]


def test_video_corrupt_budget_skips_frame_and_counts(tmp_path):
    """ISSUE satellite (ROADMAP reliability item): a per-frame decode error
    under corrupt_record_budget becomes a SKIPPED frame (zero payload,
    vid masks False — the shape the model already handles), counted on
    hbnlp_corrupt_records_total{pipeline="video"}; alignment and batch
    count are unaffected."""
    pytest.importorskip("cv2")
    from homebrewnlp_tpu.data.video import VideoPipeline
    from homebrewnlp_tpu.obs.registry import REGISTRY
    cfg = mixer_config(model_mode="jannet", use_video=True, use_language=False,
                       frame_height=32, frame_width=32, patch_size=16,
                       sequence_length=4, experts=1, corrupt_record_budget=3)
    paths = _write_video_shard_with_bad_frame(tmp_path, cfg, 12, bad_index=6)
    counter = REGISTRY.counter("hbnlp_corrupt_records_total",
                               labelnames=("pipeline",))
    before = counter.value(pipeline="video")
    pipe = VideoPipeline(cfg, sub_batch_size=2, paths=paths)
    it = iter(pipe)
    batch = next(it)
    assert counter.value(pipeline="video") == before + 1
    assert pipe.budget is not None and pipe.budget.spent == 1
    # windows 0 and 1 cover frames 0..4 and 4..8: the bad frame (6) lands in
    # window 1 at position 2, masked exactly like a real skip-frame
    assert batch["frame"].shape[0] == 2
    assert not batch["vid_msk_src"][1].all()
    assert batch["vid_msk_src"][0].all()
    # the substituted frame is all-zero payload
    assert (batch["frame"][1][2] == 0).all()


def test_video_strict_without_budget_raises(tmp_path):
    pytest.importorskip("cv2")
    from homebrewnlp_tpu.data.video import VideoPipeline
    cfg = mixer_config(model_mode="jannet", use_video=True, use_language=False,
                       frame_height=32, frame_width=32, patch_size=16,
                       sequence_length=4, experts=1, corrupt_record_budget=0)
    paths = _write_video_shard_with_bad_frame(tmp_path, cfg, 12, bad_index=2)
    with pytest.raises(ValueError, match="undecodable"):
        next(iter(VideoPipeline(cfg, sub_batch_size=2, paths=paths)))


def test_video_budget_exhaustion_raises(tmp_path):
    """A rotting shard (more bad frames than budget) must surface, not be
    papered over."""
    pytest.importorskip("cv2")
    import cv2
    from homebrewnlp_tpu.data.video import VideoPipeline
    cfg = mixer_config(model_mode="jannet", use_video=True, use_language=False,
                       frame_height=32, frame_width=32, patch_size=16,
                       sequence_length=4, experts=1, corrupt_record_budget=1)
    rng = np.random.default_rng(5)
    path = str(tmp_path / "video0000.tfrecord")
    with RecordWriter(path) as w:
        for j in range(12):
            if j in (3, 4):
                frame_bytes = b"garbage"
            else:
                ok, enc = cv2.imencode(".jpg", rng.integers(
                    0, 256, (cfg.frame_height, cfg.frame_width,
                             cfg.color_channels), np.uint8))
                frame_bytes = enc.tobytes()
            w.write(encode_example({"frame": frame_bytes,
                                    "concat": [int(j == 0)],
                                    "skip_frame": [0]}))
    with pytest.raises(OSError, match="budget exhausted"):
        list(VideoPipeline(cfg, sub_batch_size=2, paths=[path]))


def test_video_parallel_decode_matches_serial(tmp_path):
    cv2 = pytest.importorskip("cv2")
    from homebrewnlp_tpu.data import write_video_tfrecords
    from homebrewnlp_tpu.data.video import VideoPipeline
    cfg_s = mixer_config(model_mode="jannet", use_video=True,
                         use_language=False, frame_height=32, frame_width=32,
                         patch_size=16, sequence_length=4, experts=1)
    cfg_p = mixer_config(model_mode="jannet", use_video=True,
                         use_language=False, frame_height=32, frame_width=32,
                         patch_size=16, sequence_length=4, experts=1,
                         parallel_interleave=4)
    paths = write_video_tfrecords(str(tmp_path), 1, 25, cfg_s, seed=7)
    serial = []
    it_s = iter(VideoPipeline(cfg_s, sub_batch_size=2, paths=paths))
    for _ in range(3):
        serial.append(next(it_s))
    par_pipe = VideoPipeline(cfg_p, sub_batch_size=2, paths=paths)
    assert par_pipe._workers == 4
    parallel = []
    it = iter(par_pipe)
    for _ in range(len(serial)):
        parallel.append(next(it))
    for a, b in zip(serial, parallel):
        for k in a:
            np.testing.assert_array_equal(a[k], b[k], err_msg=k)


def test_prefetcher_passthrough_and_resume(tmp_path):
    from homebrewnlp_tpu.data.pipeline import Prefetcher
    paths = write_text_tfrecords(str(tmp_path), 3, 4, 64, seed=5)
    cfg = mixer_config(sequence_length=16)

    plain = GptPipeline(cfg, sub_batch_size=2, paths=paths)
    want = [dict(b) for _, b in zip(range(6), plain)]

    pre = Prefetcher(GptPipeline(cfg, sub_batch_size=2, paths=paths), depth=3)
    it = iter(pre)
    got = [next(it) for _ in range(4)]
    state = pre.state_dict()
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a["token_x"], b["token_x"])

    # resume: state reflects the last *delivered* batch, not queue contents
    pre2 = Prefetcher(GptPipeline(cfg, sub_batch_size=2, paths=paths), depth=3)
    pre2.load_state_dict(state)
    it2 = iter(pre2)
    np.testing.assert_array_equal(next(it2)["token_x"], want[4]["token_x"])
    np.testing.assert_array_equal(next(it2)["token_x"], want[5]["token_x"])


def test_device_feeder_matches_sync_order(tmp_path, eight_devices):
    """Background-thread device prefetch delivers the exact batch sequence
    of the synchronous (depth=0) path — ordering is a correctness invariant
    (ISSUE 2 prefetcher coverage)."""
    from homebrewnlp_tpu.data.feed import DeviceFeeder
    from homebrewnlp_tpu.parallel import make_mesh
    cfg = mixer_config(interleaved_datasets=2)
    paths = write_text_tfrecords(str(tmp_path), 3, 2, 100, seed=5)
    mesh = make_mesh(cfg)
    sync = DeviceFeeder(iter(GptPipeline(cfg, 2, paths=paths)), cfg, mesh,
                        depth=0)
    want = [np.asarray(next(sync)["token_x"].x).copy() for _ in range(5)]
    feeder = DeviceFeeder(iter(GptPipeline(cfg, 2, paths=paths)), cfg, mesh,
                          depth=2)
    got = [np.asarray(next(feeder)["token_x"].x).copy() for _ in range(5)]
    feeder.close()
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)


def test_device_feeder_stopiteration_and_shutdown(tmp_path, eight_devices):
    """Exhaustion propagates as StopIteration (after every real batch was
    delivered) and close() leaves no live producer thread."""
    import threading
    from homebrewnlp_tpu.data.feed import DeviceFeeder
    from homebrewnlp_tpu.parallel import make_mesh
    cfg = mixer_config(interleaved_datasets=1)
    # 1 file x 1 record x 70 tokens -> 4 windows -> two 2-row batches
    paths = write_text_tfrecords(str(tmp_path), 1, 1, 70, seed=3)
    mesh = make_mesh(cfg)
    feeder = DeviceFeeder(iter(GptPipeline(cfg, 2, paths=paths)), cfg, mesh,
                          depth=2)
    batches = []
    with pytest.raises(StopIteration):
        for _ in range(10):
            batches.append(next(feeder))
    assert len(batches) == 2
    # iterator contract: exhaustion re-raises on EVERY later next() — the
    # one-shot DONE sentinel must not leave a second call deadlocked on an
    # empty queue with a dead producer
    with pytest.raises(StopIteration):
        next(feeder)
    feeder.close()
    assert not any(t.name == "device-feeder" and t.is_alive()
                   for t in threading.enumerate())
    # a producer-side error (not exhaustion) surfaces to the consumer too
    def boom():
        yield {"token_x": np.zeros((2, 16, 1), np.int32),
               "token_y": np.zeros((2, 16, 1), np.int32)}
        raise RuntimeError("decode failed")
    f2 = DeviceFeeder(boom(), cfg, mesh, depth=1)
    next(f2)
    with pytest.raises(RuntimeError, match="decode failed"):
        next(f2)
    with pytest.raises(RuntimeError, match="decode failed"):
        next(f2)  # errors also re-raise instead of deadlocking
    f2.close()


def test_device_feeder_resume_cursor_consumed_only(tmp_path, eight_devices):
    """state_dict under prefetch depth 2 reflects CONSUMED batches only:
    resuming from it continues with exactly the next undelivered batch,
    even though the producer ran ahead."""
    from homebrewnlp_tpu.data.feed import DeviceFeeder
    from homebrewnlp_tpu.parallel import make_mesh
    cfg = mixer_config(interleaved_datasets=2)
    paths = write_text_tfrecords(str(tmp_path), 3, 2, 120, seed=9)
    mesh = make_mesh(cfg)
    want = [b["token_x"].copy()
            for _, b in zip(range(6), GptPipeline(cfg, 2, paths=paths))]

    pipe = GptPipeline(cfg, 2, paths=paths)
    feeder = DeviceFeeder(iter(pipe), cfg, mesh, depth=2,
                          state_fn=pipe.state_dict)
    for i in range(3):
        np.testing.assert_array_equal(
            np.asarray(next(feeder)["token_x"].x), want[i])
    state = feeder.state_dict()
    feeder.close()

    pipe2 = GptPipeline(cfg, 2, paths=paths)
    pipe2.load_state_dict(state)
    feeder2 = DeviceFeeder(iter(pipe2), cfg, mesh, depth=2,
                           state_fn=pipe2.state_dict)
    for i in (3, 4, 5):
        np.testing.assert_array_equal(
            np.asarray(next(feeder2)["token_x"].x), want[i])
    feeder2.close()


def test_prefetcher_close_joins_blocked_producer(tmp_path):
    """Prefetcher.close() unjams a producer parked on a full queue and
    wakes a consumer parked on an empty one (the async loop's shutdown
    path)."""
    import threading
    from homebrewnlp_tpu.data.pipeline import Prefetcher
    paths = write_text_tfrecords(str(tmp_path), 3, 4, 64, seed=5)
    cfg = mixer_config(sequence_length=16)
    before = {id(t) for t in threading.enumerate()}
    pre = Prefetcher(GptPipeline(cfg, sub_batch_size=2, paths=paths), depth=1)
    it = iter(pre)
    next(it)  # starts the producer; queue depth 1 fills, producer parks
    pre.close()
    leaked = [t for t in threading.enumerate()
              if id(t) not in before and t.is_alive()]
    assert not leaked


def test_remote_fs_tfrecord_roundtrip():
    """TFRecord write/read/glob through a remote (memory://) filesystem —
    the gs:// path type-checks through the same fsspec route."""
    fsspec = pytest.importorskip("fsspec")
    from homebrewnlp_tpu.data import fs
    from homebrewnlp_tpu.data.tfrecord import RecordWriter

    base = "memory://bucket/shards"
    for i in range(2):
        with RecordWriter(f"{base}/part{i}_128.tfrecord") as w:
            w.write(encode_example({"text": bytes(range(10))}))
            w.write(encode_example({"text": bytes(range(10, 20))}))

    found = sorted(fs.glob(f"{base}/part*_128.tfrecord"))
    assert len(found) == 2 and all(p.startswith("memory://") for p in found)
    payloads = list(read_records(found[0], verify=True))
    assert len(payloads) == 2
    ex = decode_example(payloads[1])
    assert ex["text"][0] == bytes(range(10, 20))
    assert count_records(found[1]) == 2


def test_remote_fs_pipeline_reads_remote_glob():
    fsspec = pytest.importorskip("fsspec")
    from homebrewnlp_tpu.data.tfrecord import RecordWriter
    rng = np.random.default_rng(0)
    for i in range(2):
        with RecordWriter(f"memory://data/sh{i}_256.tfrecord") as w:
            w.write(encode_example(
                {"text": bytes(rng.integers(0, 255, 256, np.uint8).tolist())}))
    cfg = mixer_config(sequence_length=16, dataset_configs=[
        {"type": "text", "path": "memory://data/sh*_256.tfrecord"}])
    pipe = GptPipeline(cfg, sub_batch_size=2)
    batch = next(iter(pipe))
    assert batch["token_x"].shape == (2, 16, 1)


def test_put_with_retry_memory():
    fsspec = pytest.importorskip("fsspec")
    import tempfile, os
    from homebrewnlp_tpu.data import fs
    with tempfile.NamedTemporaryFile(delete=False) as f:
        f.write(b"payload")
        local = f.name
    try:
        fs.put_with_retry(local, "memory://up/loads/x.bin", retries=2)
        with fs.open_stream("memory://up/loads/x.bin") as r:
            assert r.read() == b"payload"
        fs.write_with_retry("memory://up/loads/y.txt", b"hi")
        with fs.open_stream("memory://up/loads/y.txt") as r:
            assert r.read() == b"hi"
    finally:
        os.unlink(local)


def test_local_row_slice_two_process_layout():
    """Property test of the multi-host feed arithmetic against a simulated
    2-process x 4-device layout: reassembling every device's slice from the
    per-process local batches must reproduce the global batch exactly."""
    from homebrewnlp_tpu.data.feed import local_row_slice

    global_rows, n_proc = 8, 2
    local = global_rows // n_proc  # 4 rows per process
    data = np.arange(global_rows * 3).reshape(global_rows, 3)
    host_batches = [data[p * local:(p + 1) * local] for p in range(n_proc)]

    # 8 devices, data axis 8: each device requests one global row; devices
    # 0-3 live on process 0, 4-7 on process 1.  The caller passes each
    # process's span start (data/feed.py::to_global derives it from the
    # process's data-axis coordinates)
    for dev in range(8):
        index = (slice(dev, dev + 1), slice(None))
        proc = dev // 4
        rows = local_row_slice(index, local, global_rows, proc * local)
        np.testing.assert_array_equal(host_batches[proc][rows],
                                      data[dev:dev + 1])

    # data axis 4 (2 rows per device), 2 devices per process
    for dev in range(4):
        index = (slice(dev * 2, dev * 2 + 2), slice(None))
        proc = dev // 2
        rows = local_row_slice(index, local, global_rows, proc * local)
        np.testing.assert_array_equal(host_batches[proc][rows],
                                      data[dev * 2:dev * 2 + 2])

    # a request outside the process's span is rejected, not silently wrong
    with pytest.raises(ValueError):
        local_row_slice((slice(2, 6), slice(None)), local, global_rows, 4)

    # replicated batch (no data sharding): every device asks for everything —
    # only valid single-process; the span guard fires for 2 procs
    with pytest.raises(ValueError):
        local_row_slice((slice(0, 8), slice(None)), local, global_rows, 0)
    assert local_row_slice((slice(0, 8), slice(None)), 8, 8) == slice(0, 8)


def test_metric_writer_scalars_and_histograms(tmp_path):
    import json as jsonlib
    from homebrewnlp_tpu.train.metrics import MetricWriter
    w = MetricWriter(str(tmp_path))
    w.write(0, {"loss": 1.5, "grad_hist/x": np.array([0, 3, 5, 1]),
                "grad_norm/x": np.float32(2.0)})
    w.close()
    line = jsonlib.loads((tmp_path / "metrics.jsonl").read_text().splitlines()[0])
    assert line["loss"] == 1.5 and line["grad_norm/x"] == 2.0
    assert "grad_hist/x" not in line  # vectors go to TB only


def test_bench_guard_threshold_logic():
    """bench.evaluate_guard: the 10k-step acceptance run's thresholds at
    full length (7.71 -> 3.45@100 -> 2.76@300, bench.py docstring),
    reach-what-you-ran semantics for short development runs."""
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from bench import evaluate_guard

    def rows(pairs):
        return [{"step": s, "loss": l} for s, l in pairs]

    healthy = rows([(1, 7.71), (60, 3.9), (120, 3.3), (300, 2.8)])
    assert evaluate_guard(healthy, 300)["pass"]
    # short dev run: only the reached checkpoints are asserted
    assert evaluate_guard(rows([(1, 7.77), (50, 5.9)]), 50)["pass"]
    # not decreasing -> fail even short
    assert not evaluate_guard(rows([(1, 7.77), (50, 7.9)]), 50)["pass"]
    # bad init (loaded checkpoint instead of fresh) -> fail
    assert not evaluate_guard(rows([(1, 3.0), (300, 2.5)]), 300)["pass"]
    # the LR-0.01 instability signature (regression toward 5-8 after
    # warmup, bench.py docstring) -> fail at full length
    stalled = rows([(1, 7.77), (120, 5.7), (300, 5.7)])
    assert not evaluate_guard(stalled, 300)["pass"]
    # stalls above the 300-step bar -> fail
    assert not evaluate_guard(rows([(1, 7.71), (120, 4.2), (300, 4.0)]),
                              300)["pass"]


def test_bench_guard_refuses_synthetic_fallback(tmp_path):
    """bench.ensure_real_corpus: missing corpus triggers the injectable
    builder; a builder that fails (or produces nothing) yields a structured
    refusal instead of letting the guard train on synthetic noise (the
    round-5 post-mortem, docs/perf/README.md round 5d)."""
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from bench import ensure_real_corpus

    pattern = str(tmp_path / "corpus" / "*.tfrecord")
    # builder that fails outright -> structured error, no exception
    res = ensure_real_corpus(pattern,
                             builder=lambda: (_ for _ in ()).throw(
                                 RuntimeError("roots missing")))
    assert res is not None and not res["pass"] and "rebuild failed" in res["error"]
    # builder that "succeeds" but produces nothing -> refusal
    res = ensure_real_corpus(pattern, builder=lambda: None)
    assert res is not None and not res["pass"] and "synthetic" in res["error"]
    # builder that creates the files -> None (guard proceeds on real data)
    def build():
        os.makedirs(tmp_path / "corpus", exist_ok=True)
        (tmp_path / "corpus" / "a.tfrecord").write_bytes(b"x")
    res = ensure_real_corpus(pattern, builder=build)
    assert res is None
    # files already present -> builder not invoked
    res = ensure_real_corpus(pattern, builder=lambda: (_ for _ in ()).throw(
        AssertionError("must not be called")))
    assert res is None


def test_repeat_dataset_epoch_wraparound(tmp_path):
    """repeat_dataset=true: the sequential reader wraps deterministically at
    the epoch boundary (same window order every epoch), and the resume
    cursor keeps working across it — the reference's sequential path dies
    on exhaustion here (inputs.py:540-541)."""
    from homebrewnlp_tpu.data.synthetic import write_text_tfrecords

    cfg = mixer_config(sequence_length=8, token_patch_size=1,
                       use_random_dataloader=False, repeat_dataset=True,
                       interleaved_datasets=2)
    paths = write_text_tfrecords(str(tmp_path), n_files=2,
                                 records_per_file=1, tokens_per_record=64,
                                 seed=3)
    pipe = GptPipeline(cfg, sub_batch_size=2, paths=paths)
    it = iter(pipe)
    # one epoch = 2 files x 64 tokens -> 14 windows of 9 -> 7 batches of 2
    epoch1 = [next(it)["token_x"].copy() for _ in range(7)]
    epoch2 = [next(it)["token_x"].copy() for _ in range(7)]
    for a, b in zip(epoch1, epoch2):
        np.testing.assert_array_equal(a, b)
    # single-epoch default (reference rule): same config without the knob
    cfg1 = mixer_config(sequence_length=8, token_patch_size=1,
                        use_random_dataloader=False,
                        interleaved_datasets=2)
    it1 = iter(GptPipeline(cfg1, sub_batch_size=2, paths=paths))
    n = sum(1 for _ in it1)
    assert n == 7
