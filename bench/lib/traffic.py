"""Camera traffic for the chip benchmark: scenes, clip pools, schedules.

The scene generator is a copy of the program's ``repro.data.video``
generator (static textured background, drifting objects, sensor noise,
an optional fast bright intruder), extended for 448-px fleets:

  * person-sized textured objects instead of 12-px flat boxes;
  * a fine static texture on the background, so that a static block
    matches only at zero displacement and the codec's motion search
    reports no motion where nothing moves;
  * a panning camera (``pan`` px per frame), which moves every block;
  * frames as uint8 luma, the compact form the pool is kept in.

A traffic mix is a JSON file under ``bench/traffic/`` read by
``load_mix``; everything here is driven by its fields.  Pure numpy,
deterministic per seed.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import List

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parents[1] / "traffic"


@dataclasses.dataclass(frozen=True)
class SceneSpec:
    n_frames: int = 92
    size: int = 448
    n_objects: int = 4
    object_h: int = 48
    object_w: int = 20
    speed: float = 8.0          # px/frame, the motion-level knob
    noise: float = 1.0          # sensor noise sigma (gray levels)
    texture: float = 24.0       # amplitude of the fine static texture
    pan: float = 0.0            # px/frame the camera pans (0: fixed)
    anomaly: bool = True
    anomaly_start: int = 24
    anomaly_len: int = 16
    anomaly_speed: float = 6.0
    anomaly_size: int = 32


def _background(rng: np.random.Generator, h: int, w: int,
                texture: float) -> np.ndarray:
    """Low-frequency textured background in [40, 200] plus a fine static
    texture of amplitude ``texture`` (2-px cells)."""
    coarse = rng.uniform(40, 200, size=(h // 8 + 2, w // 8 + 2))
    ups = np.kron(coarse, np.ones((8, 8)))[:h, :w]
    # light smoothing to avoid blocky gradients
    k = np.ones((5, 5)) / 25.0
    pad = np.pad(ups, 2, mode="edge")
    out = np.zeros_like(ups)
    for dy in range(5):
        for dx in range(5):
            out += k[dy, dx] * pad[dy:dy + ups.shape[0], dx:dx + ups.shape[1]]
    fine = rng.uniform(-texture, texture, size=(h // 2 + 1, w // 2 + 1))
    out += np.kron(fine, np.ones((2, 2)))[:h, :w]
    return out


def _paste(frame: np.ndarray, cy: float, cx: float, patch: np.ndarray):
    """Paste ``patch`` centred at (cy, cx), clipped inside the frame."""
    h, w = frame.shape
    ph, pw = patch.shape
    y0 = int(np.clip(round(cy - ph / 2), 0, h - ph))
    x0 = int(np.clip(round(cx - pw / 2), 0, w - pw))
    frame[y0:y0 + ph, x0:x0 + pw] = patch


def generate_clip(spec: SceneSpec, seed: int) -> np.ndarray:
    """(T, H, W) uint8 luma of one camera segment."""
    rng = np.random.default_rng(seed)
    T, S = spec.n_frames, spec.size
    # a panning camera sees a strip of a wider scene
    extra = int(np.ceil(spec.pan * T)) + 1
    bg = _background(rng, S, S + extra, spec.texture)

    oh, ow = spec.object_h, spec.object_w
    lo = np.array([oh, ow], float)
    hi = np.array([S - oh, S - ow], float)
    pos = rng.uniform(lo, hi, size=(spec.n_objects, 2))
    vel = rng.normal(0, 1, size=(spec.n_objects, 2))
    vel = vel / (np.linalg.norm(vel, axis=1, keepdims=True) + 1e-9) * spec.speed
    # each person: dark-ish clothing with its own fine texture
    people = [rng.uniform(20, 90) + rng.uniform(-20, 20, size=(oh, ow))
              for _ in range(spec.n_objects)]

    a_pos = np.array([spec.anomaly_size, spec.anomaly_size], float)
    a_vel = np.array([spec.anomaly_speed, spec.anomaly_speed * 0.7])
    a_patch = np.full((spec.anomaly_size, spec.anomaly_size), 250.0)

    frames = np.empty((T, S, S), np.uint8)
    noise = rng.normal(0, spec.noise, size=(T, S, S)).astype(np.float32)
    for t in range(T):
        x0 = int(round(spec.pan * t))
        f = bg[:, x0:x0 + S].copy()
        for i in range(spec.n_objects):
            pos[i] += vel[i]
            for d in range(2):
                if pos[i, d] < lo[d] or pos[i, d] > hi[d]:
                    vel[i, d] *= -1
                    pos[i, d] = np.clip(pos[i, d], lo[d], hi[d])
            _paste(f, pos[i, 0], pos[i, 1], people[i])
        if (spec.anomaly and
                spec.anomaly_start <= t < spec.anomaly_start + spec.anomaly_len):
            a_pos += a_vel
            a_pos %= S
            _paste(f, a_pos[0], a_pos[1], a_patch)
        frames[t] = np.clip(np.rint(f + noise[t]), 0, 255).astype(np.uint8)
    return frames


# ----------------------------------------------------------------------
# traffic mixes
# ----------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Mix:
    """One traffic mix: a scene, a codec setting and a loop.

    ``kind`` is ``sessions`` (each camera replays segments of
    ``segment_windows`` windows back to back; a finished segment is a
    camera leaving and the next one joining) or ``clips`` (one-window
    clips, each its own stream).  Both are closed loops with a fixed
    number of streams in flight, set per cell.
    """

    name: str
    kind: str
    codec: dict
    scene: SceneSpec
    segment_windows: int = 20
    stagger: bool = True
    pool_per_stream: int = 1
    scene_seed: int = 0

    @property
    def window(self) -> int:
        return int(self.codec["window_frames"])

    @property
    def stride(self) -> int:
        return int(self.codec["stride_frames"])

    def frames_for(self, n_windows: int) -> int:
        return self.window + (n_windows - 1) * self.stride


def load_mix(name: str, directory: Path = TRAFFIC_DIR) -> Mix:
    raw = json.loads((directory / f"{name}.json").read_text())
    kind = raw["kind"]
    if kind not in ("sessions", "clips"):
        raise ValueError(f"traffic {name}: unknown kind {kind!r}")
    if raw.get("loop") != "closed":
        raise ValueError(f"traffic {name}: only closed loops are supported")
    seg = int(raw.get("segment_windows", 1))
    codec = dict(raw["codec"])
    frames = codec["window_frames"] + (seg - 1) * codec["stride_frames"]
    scene = SceneSpec(n_frames=frames, **raw["scene"])
    return Mix(name=name, kind=kind, codec=codec, scene=scene,
               segment_windows=seg, stagger=bool(raw.get("stagger", False)),
               pool_per_stream=int(raw.get("pool_per_stream", 1)),
               scene_seed=int(raw.get("scene_seed", 0)))


def clip_seed(seed: int, index: int) -> int:
    """Seed of pool clip ``index`` for run seed ``seed`` (any size)."""
    return int(np.random.SeedSequence([seed % 2**63, index]).generate_state(1)[0])


def build_pool(mix: Mix, n_streams: int) -> List[np.ndarray]:
    """The run's whole clip pool, uint8, made before the window: one
    scene (``pool_per_stream`` scenes) per stream slot.

    The scenes are the mix's own (``scene_seed``), the same for every
    run seed, so that every seed serves the same sizes.  The packed
    vision tower compiles one program per packing geometry, and the
    geometries follow the scenes' motion: scenes drawn from the run seed
    bring new programs with every seed, some 20 s of compiling each, and
    runs then overran their time.  The run seed draws the weights and
    the sample that is checked."""
    n = n_streams * mix.pool_per_stream
    return [generate_clip(mix.scene, clip_seed(mix.scene_seed, i))
            for i in range(n)]


@dataclasses.dataclass(frozen=True)
class Segment:
    """What one stream submission replays: frames [first_frame:] of pool
    clip ``clip`` (so ``windows`` windows), for camera ``camera``."""

    camera: int
    clip: int
    first_window: int
    windows: int


class Schedule:
    """Closed-loop schedule: which segment each stream slot plays next.

    Sessions: camera ``c`` replays pool clip ``c`` (``pool_per_stream``
    clips each, in turn).  With ``stagger`` its first segment starts at
    window ``round(c * L / F)`` of its clip, so that the segment ends,
    and the next cameras' joins, are spread evenly over the fleet
    instead of arriving together.  Clips: slot ``c`` cycles through the
    pool clips ``c, c + F, c + 2F, ...``.
    """

    def __init__(self, mix: Mix, n_streams: int):
        self.mix = mix
        self.n = n_streams
        self._count = [0] * n_streams

    def first(self) -> List[Segment]:
        return [self.next(c) for c in range(self.n)]

    def next(self, camera: int) -> Segment:
        k = self._count[camera]
        self._count[camera] += 1
        m, L = self.mix, self.mix.segment_windows
        clip = camera + self.n * (k % m.pool_per_stream)
        if m.kind == "clips":
            return Segment(camera, clip, 0, 1)
        start = (round(camera * L / self.n) if (m.stagger and k == 0) else 0)
        return Segment(camera, clip, start, L - start)

    def frames(self, seg: Segment, pool: List[np.ndarray]) -> np.ndarray:
        lo = seg.first_window * self.mix.stride
        return pool[seg.clip][lo: lo + self.mix.frames_for(seg.windows)]
