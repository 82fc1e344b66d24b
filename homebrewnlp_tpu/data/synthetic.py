"""Synthetic data: deterministic token batches + TFRecord fixture writers.

The reference has no test data story (SURVEY.md §4); these helpers back the
test suite and bench.py, and double as the format reference for the real
TFRecord writers in tools/.
"""
from __future__ import annotations

import os
import typing

import numpy as np

from ..config import Config
from .tfrecord import RecordWriter, encode_example


def synthetic_text_batch(cfg: Config, step: int = 0, seed: int = 0
                         ) -> typing.Dict[str, np.ndarray]:
    rng = np.random.default_rng((seed, step))
    rows = cfg.sequence_length // cfg.token_patch_size
    # macro-batching inflates the host batch (reference
    # dataloader_placement.py:40-44)
    shape = (cfg.train_batch_size * cfg.macro_batching,
             rows + cfg.output_offset, cfg.token_patch_size)
    stream = rng.integers(0, cfg.vocab_size, shape, np.int32)
    return {"token_x": stream[:, :rows],
            "token_y": stream[:, cfg.output_offset:rows + cfg.output_offset]}


def synthetic_video_batch(cfg: Config, step: int = 0, seed: int = 0
                          ) -> typing.Dict[str, np.ndarray]:
    """Random jannet-mode batch matching VideoPipeline's output shapes."""
    rng = np.random.default_rng((seed, step, 7))
    b = cfg.train_batch_size * cfg.macro_batching
    t = cfg.time_patch_size
    frame_shape = ((b, t + 1, cfg.frame_height_patch, cfg.frame_width_patch,
                    cfg.channel_color_size) if cfg.three_axes else
                   (b, t + 1, cfg.frame_height_patch * cfg.frame_width_patch,
                    cfg.channel_color_size))
    out = {
        "frame": rng.integers(0, 256, frame_shape, np.int32),
        "vid_msk_src": np.ones((b, t), bool),
        "vid_msk_tgt": np.ones((b, t), bool),
        "cat_mask_x": np.ones((b, t), bool),
        "cat_mask_y": np.ones((b, t), bool),
    }
    if cfg.use_language and cfg.language_token_per_frame > 0:
        toks = rng.integers(0, cfg.vocab_size,
                            (b, t + 1, cfg.language_token_patch,
                             cfg.token_patch_size), np.int32)
        out["token_x"] = toks[:, :t]
        out["token_y"] = toks[:, 1:t + 1]
        out["txt_msk"] = np.ones_like(out["token_y"], bool)
    return out


def learnable_tokens(rng: np.random.Generator, n_tokens: int
                     ) -> np.ndarray:
    """``n_tokens`` bytes of a seeded toy language: words drawn Zipf-wise
    from a fixed lexicon of 64 lowercase words, separated by spaces.  Unlike
    uniform noise — which pins any model at ln(vocab) and makes a loss check
    meaningless — its byte distribution is far from uniform (27 of 256
    symbols) and its words repeat, so a byte-level model's loss falls below
    ln(256) within a few updates and keeps falling."""
    lex_rng = np.random.default_rng(0)  # the lexicon never changes
    letters = np.arange(ord("a"), ord("z") + 1)
    letter_p = 1.0 / np.arange(1, len(letters) + 1)
    letter_p /= letter_p.sum()
    words = [bytes(lex_rng.choice(letters, size=int(lex_rng.integers(2, 9)),
                                  p=letter_p).tolist()) + b" "
             for _ in range(64)]
    word_p = 1.0 / np.arange(1, len(words) + 1)
    word_p /= word_p.sum()
    out = bytearray()
    while len(out) < n_tokens:
        for i in rng.choice(len(words), size=256, p=word_p):
            out += words[i]
    return np.frombuffer(bytes(out[:n_tokens]), np.uint8)


def write_text_tfrecords(directory: str, n_files: int, records_per_file: int,
                         tokens_per_record: int, vocab: int = 256,
                         seed: int = 0, int64: bool = False,
                         draw: typing.Optional[typing.Callable[
                             [np.random.Generator, int], np.ndarray]] = None
                         ) -> typing.List[str]:
    """Write synthetic text shards; filenames carry the token count the way
    the reference's run-log replay expects (``..._<n_tokens>.tfrecord``,
    inputs.py:34).  ``draw(rng, n)`` supplies each record's tokens (default:
    uniform noise over ``vocab``; :func:`learnable_tokens` for a stream a
    model can learn)."""
    rng = np.random.default_rng(seed)
    if draw is None:
        def draw(rng, n):
            return rng.integers(0, vocab, n)
    os.makedirs(directory, exist_ok=True)
    paths = []
    total = records_per_file * tokens_per_record
    for i in range(n_files):
        kind = "int64" if int64 else "bytes"
        path = os.path.join(directory, f"shard{kind}{i:04d}_{total}.tfrecord")
        with RecordWriter(path) as w:
            for _ in range(records_per_file):
                tokens = draw(rng, tokens_per_record)
                if int64:
                    w.write(encode_example({"text": [int(t) for t in tokens]}))
                else:
                    w.write(encode_example(
                        {"text": bytes(tokens.astype(np.uint8).tolist())}))
        paths.append(path)
    return paths


def write_video_tfrecords(directory: str, n_files: int, frames_per_file: int,
                          cfg: Config, seed: int = 0) -> typing.List[str]:
    """Synthetic video shards with JPEG frames + concat/skip flags (+ tokens
    when language_token_per_frame > 0)."""
    import cv2
    rng = np.random.default_rng(seed)
    os.makedirs(directory, exist_ok=True)
    paths = []
    for i in range(n_files):
        path = os.path.join(directory, f"video{i:04d}.tfrecord")
        with RecordWriter(path) as w:
            for j in range(frames_per_file):
                img = rng.integers(0, 256, (cfg.frame_height, cfg.frame_width,
                                            cfg.color_channels), np.uint8)
                ok, enc = cv2.imencode(".jpg", img)
                assert ok
                feats: typing.Dict[str, typing.Any] = {
                    "frame": enc.tobytes(),
                    "concat": [int(j == 0)],
                    "skip_frame": [0],
                }
                if cfg.language_token_per_frame > 0:
                    feats["tokens"] = [int(t) for t in rng.integers(
                        0, cfg.vocab_size, cfg.language_token_per_frame)]
                    feats["mask"] = [int(cfg.language_token_per_frame)]
                w.write(encode_example(feats))
        paths.append(path)
    return paths
