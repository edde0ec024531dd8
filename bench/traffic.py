"""Open-loop 4K camera traffic: the one generator every traffic mix runs.

A mix is a data file under ``bench/traffic/`` (see :func:`load_mix`).
This module copies the repository's own sound generators so that no
later change to the program can move the yardstick:

* ``Scene`` / ``SCENE_PRESETS`` / ``preset`` — ``repro.data.synthetic``
  (the paper's Table I scenes), with the background computed in float32;
* ``partition_host`` — ``repro.core.partitioning`` (Alg. 1: zones of max
  overlap, enclosing rectangles, sizes aligned up, clamped to the frame);
* ``Uplink`` / ``patch_bytes`` — ``repro.data.video`` (FIFO uplink per
  camera, bits-per-pixel byte model).

The GMM half of the edge pipeline is not run: it runs on the cameras, not
in the cloud function.  Patches are cut from each scene's ground-truth
boxes at the mix's frame size, and each is clamped to the canvas as the
edge pipeline does.

Every seed gets the same work in the same order: each camera sends the
scene's steps ``burn_in .. burn_in + n - 1`` in scene order, one frame
every camera period (the scene itself is seeded by its preset, not by
``--seed``).  The seed draws only each camera's phase: frame ``k`` of a
camera is generated at ``(k + phase) / fps``, with the phase uniform in
``[0, 1)``, as unsynchronised cameras start.  So runs with different
seeds differ in how the cameras' frames interleave, and so in how the
invoker batches them, not in the patches sent.
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from typing import Dict, List, Sequence

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"

# (name, n_objects, mean object side in px at 4K, roi proportion target %)
SCENE_PRESETS = [
    ("university_canteen", 25, 90, 5.45),
    ("oct_habour", 38, 90, 8.31),
    ("xili_crossroad", 55, 60, 5.91),
    ("primary_school", 24, 140, 14.16),
    ("basketball_court", 11, 120, 5.04),
    ("xinzhongguan", 90, 45, 5.23),
    ("university_campus", 25, 55, 2.59),
    ("xili_street_1", 48, 80, 9.63),
    ("xili_street_2", 30, 95, 8.75),
    ("huaqiangbei", 120, 50, 9.67),
]
PRESET_INDEX = {name: i for i, (name, *_rest) in enumerate(SCENE_PRESETS)}

ACTIVE_FRAC = 0.86
_LOGNORM_AREA = 1.38

# transmission byte model (repro.data.video)
BPP_FG = 0.25
HEADER_BYTES = 256


@dataclasses.dataclass
class SceneConfig:
    name: str
    width: int = 960
    height: int = 540
    n_objects: int = 30
    obj_side: int = 24
    fps: float = 10.0
    seed: int = 0
    speed: float = 3.0
    burst_prob: float = 0.02
    n_clusters: int = 3
    cluster_pull: float = 0.02


def preset(index: int, width: int, height: int) -> SceneConfig:
    """Calibrate the mean object size to the scene's Table-I RoI share."""
    name, n_obj, _side4k, prop_pct = SCENE_PRESETS[index % len(SCENE_PRESETS)]
    target_area = prop_pct / 100.0 * width * height
    mean_area = target_area / (n_obj * ACTIVE_FRAC * _LOGNORM_AREA)
    side = max(4, int(mean_area ** 0.5))
    return SceneConfig(name=name, width=width, height=height,
                       n_objects=n_obj, obj_side=side, seed=index)


class Scene:
    """Moving-rectangle scene with a textured static background."""

    def __init__(self, cfg: SceneConfig):
        self.cfg = cfg
        rng = np.random.default_rng(cfg.seed)
        h, w = cfg.height, cfg.width
        yy = np.arange(h, dtype=np.float32)[:, None]
        xx = np.arange(w, dtype=np.float32)[None, :]
        self.background = (
            0.35 + 0.15 * np.sin(xx / 37.0) * np.cos(yy / 23.0)
            + 0.05 * rng.standard_normal((h, w), dtype=np.float32)
        ).astype(np.float32).clip(0.0, 1.0)
        n = cfg.n_objects
        self.centers = rng.uniform([w * .15, h * .15], [w * .85, h * .85],
                                   size=(cfg.n_clusters, 2)).astype(np.float32)
        assign = rng.integers(0, cfg.n_clusters, n)
        self.home = self.centers[assign]
        spread = min(w, h) / 8.0
        self.pos = (self.home + rng.normal(0, spread, (n, 2))
                    ).astype(np.float32).clip([0, 0], [w, h])
        self.vel = rng.normal(0, cfg.speed, size=(n, 2)).astype(np.float32)
        sides = rng.lognormal(np.log(cfg.obj_side), 0.4, size=(n, 2))
        self.size = np.clip(sides, 4, min(h, w) // 3).astype(np.float32)
        self.shade = rng.uniform(0.6, 1.0, size=n).astype(np.float32)
        self.active = np.ones(n, bool)
        self._rng = rng
        self.t = 0

    def step(self):
        cfg = self.cfg
        n = len(self.pos)
        self.vel += self._rng.normal(0, 0.5, size=(n, 2)).astype(np.float32)
        self.vel += cfg.cluster_pull * (self.home - self.pos)
        self.vel = np.clip(self.vel, -3 * cfg.speed, 3 * cfg.speed)
        self.pos += self.vel
        for d, limit in ((0, cfg.width), (1, cfg.height)):
            low = self.pos[:, d] < 0
            high = self.pos[:, d] > limit
            self.vel[low | high, d] *= -1
            self.pos[:, d] = np.clip(self.pos[:, d], 0, limit)
        r = self._rng.random(n)
        turn_off = self.active & (r < cfg.burst_prob)
        turn_on = ~self.active & (r < 6 * cfg.burst_prob)
        self.active = (self.active & ~turn_off) | turn_on
        if not self.active.any():
            self.active[0] = True
        self.t += 1

    def boxes(self) -> np.ndarray:
        """Ground-truth boxes (K, 4) xyxy of active objects."""
        w2 = self.size[:, 0] / 2
        h2 = self.size[:, 1] / 2
        b = np.stack([self.pos[:, 0] - w2, self.pos[:, 1] - h2,
                      self.pos[:, 0] + w2, self.pos[:, 1] + h2], axis=-1)
        b[:, 0::2] = b[:, 0::2].clip(0, self.cfg.width)
        b[:, 1::2] = b[:, 1::2].clip(0, self.cfg.height)
        b = b[self.active]
        keep = (b[:, 2] - b[:, 0] > 2) & (b[:, 3] - b[:, 1] > 2)
        return b[keep].astype(np.int32)

    def render_rgb(self) -> np.ndarray:
        """RGB frame (H, W, 3) float32 with the active objects drawn."""
        frame = self.background.copy()
        for i in np.nonzero(self.active)[0]:
            x0 = int(max(0, self.pos[i, 0] - self.size[i, 0] / 2))
            y0 = int(max(0, self.pos[i, 1] - self.size[i, 1] / 2))
            x1 = int(min(self.cfg.width, self.pos[i, 0] + self.size[i, 0] / 2))
            y1 = int(min(self.cfg.height, self.pos[i, 1] + self.size[i, 1] / 2))
            if x1 > x0 and y1 > y0:
                frame[y0:y1, x0:x1] = self.shade[i]
        rgb = np.empty(frame.shape + (3,), np.float32)
        rgb[..., 0] = frame
        np.multiply(frame, 0.9, out=rgb[..., 1])
        np.multiply(frame, 0.8, out=rgb[..., 2])
        return rgb


@dataclasses.dataclass(frozen=True)
class Patch:
    """A cut-out region, as ``repro.core.partitioning.Patch`` has it."""
    x0: int
    y0: int
    x1: int
    y1: int
    frame_id: int = 0
    camera_id: int = 0
    t_gen: float = 0.0
    slo: float = 1.0

    @property
    def w(self) -> int:
        return self.x1 - self.x0

    @property
    def h(self) -> int:
        return self.y1 - self.y0


def partition_host(boxes: np.ndarray, frame_w: int, frame_h: int,
                   zone_x: int, zone_y: int, align: int) -> List[tuple]:
    """Alg. 1 on the host: (x0, y0, x1, y1) of each non-empty zone."""
    if len(boxes) == 0:
        return []
    zw, zh = frame_w // zone_x, frame_h // zone_y
    zones: dict = {}
    for (x0, y0, x1, y1) in boxes:
        best, best_area = None, 0
        for zyi in range(zone_y):
            for zxi in range(zone_x):
                ox = max(0, min(x1, (zxi + 1) * zw) - max(x0, zxi * zw))
                oy = max(0, min(y1, (zyi + 1) * zh) - max(y0, zyi * zh))
                if ox * oy > best_area:
                    best_area = ox * oy
                    best = zyi * zone_x + zxi
        if best is None:
            continue
        zones.setdefault(best, []).append((x0, y0, x1, y1))
    out = []
    for _z, bs in sorted(zones.items()):
        x0 = min(b[0] for b in bs)
        y0 = min(b[1] for b in bs)
        x1 = max(b[2] for b in bs)
        y1 = max(b[3] for b in bs)
        w = int(np.ceil((x1 - x0) / align) * align)
        h = int(np.ceil((y1 - y0) / align) * align)
        x1 = min(x0 + w, frame_w)
        x0 = max(x1 - w, 0)
        y1 = min(y0 + h, frame_h)
        y0 = max(y1 - h, 0)
        out.append((int(x0), int(y0), int(x1), int(y1)))
    return out


def patch_bytes(p: Patch) -> float:
    return HEADER_BYTES + p.w * p.h * BPP_FG


class Uplink:
    """One camera's FIFO uplink: arrival = max(t_gen, link free) + bytes/bw."""

    def __init__(self, bandwidth_bps: float):
        if bandwidth_bps <= 0:
            raise ValueError(f"bandwidth must be positive, got {bandwidth_bps}")
        self.byte_rate = bandwidth_bps / 8.0
        self.link_free = 0.0

    def send(self, p: Patch) -> float:
        t_arr = max(p.t_gen, self.link_free) + patch_bytes(p) / self.byte_rate
        self.link_free = t_arr
        return t_arr


# ------------------------------------------------------------------ mixes ----

#: keys every traffic file holds
MIX_KEYS = ("mix", "scenes", "frame_w", "frame_h", "zones", "align",
            "fps_per_camera", "slo_s", "uplink_mbps",
            "burn_in_steps", "ring_frames")


def load_mix(name: str) -> dict:
    """The traffic file ``bench/traffic/<name>.json``, checked for its keys."""
    mix = json.loads((TRAFFIC_DIR / f"{name}.json").read_text())
    missing = [k for k in MIX_KEYS if k not in mix]
    if missing:
        raise ValueError(f"traffic {name!r} lacks {missing}")
    unknown = [s for s in mix["scenes"] if s not in PRESET_INDEX]
    if unknown:
        raise ValueError(f"traffic {name!r}: unknown scenes {unknown}")
    if mix["fps_per_camera"] <= 0 or mix["slo_s"] <= 0:
        raise ValueError(f"traffic {name!r}: rates and SLO must be positive")
    return mix


@dataclasses.dataclass
class Traffic:
    """One run's offered load: arrivals in arrival order, and the scenes
    whose frames the ring renders."""
    arrivals: List[tuple]            # (t_arrive, Patch), sorted
    scenes: List[Scene]
    n_frames: int


def camera_of(frame_id: int) -> int:
    return frame_id >> 20


def frame_index(frame_id: int) -> int:
    return frame_id & ((1 << 20) - 1)


def generate(mix: dict, seed: int, seconds: float, canvas: int,
             fps_scale: float = 1.0) -> Traffic:
    """Arrivals of one run: ``floor(seconds * fps)`` frames a camera, all
    with ``t_gen`` in ``[0, seconds)``, shaped by the camera's uplink and
    merged by arrival time.

    ``fps_scale`` multiplies the mix's frame rate (the rate sweep)."""
    rng = np.random.default_rng(seed)
    fps = mix["fps_per_camera"] * fps_scale
    zx, zy = mix["zones"]
    fw, fh = mix["frame_w"], mix["frame_h"]
    n_frames = max(1, int(math.floor(seconds * fps + 1e-9)))
    arrivals, scenes = [], []
    for cam, scene_name in enumerate(mix["scenes"]):
        scene = Scene(preset(PRESET_INDEX[scene_name], fw, fh))
        for _ in range(mix["burn_in_steps"]):
            scene.step()
        scenes.append(scene)
        phase = rng.uniform(0.0, 1.0)
        link = Uplink(mix["uplink_mbps"] * 1e6)
        for k in range(n_frames):
            scene.step()
            t_gen = (k + phase) / fps
            fid = (cam << 20) | k
            for (x0, y0, x1, y1) in partition_host(scene.boxes(), fw, fh,
                                                   zx, zy, mix["align"]):
                p = Patch(x0, y0, min(x1, x0 + canvas), min(y1, y0 + canvas),
                          frame_id=fid, camera_id=cam, t_gen=t_gen,
                          slo=mix["slo_s"])
                arrivals.append((link.send(p), p))
    arrivals.sort(key=lambda a: (a[0], a[1].camera_id))
    return Traffic(arrivals, scenes, n_frames)


def render_ring(traffic: Traffic, n: int) -> Dict[int, List[np.ndarray]]:
    """``n`` pre-rendered RGB frames per camera, reused under fresh frame
    ids (frame ``k`` of a camera shows ring frame ``k % n``)."""
    ring = {}
    for cam, scene in enumerate(traffic.scenes):
        ring[cam] = []
        for _ in range(n):
            scene.step()
            ring[cam].append(scene.render_rgb())
    return ring


def frame_pixels(ring: Dict[int, List[np.ndarray]], frame_id: int) -> np.ndarray:
    frames = ring[camera_of(frame_id)]
    return frames[frame_index(frame_id) % len(frames)]


def pow2(x: int, cap: int) -> int:
    x = max(int(x), 1)
    return min(1 << (x - 1).bit_length(), cap)


def slot_extents(patches: Sequence[Patch], canvas: int) -> tuple:
    """Pow2 slot extents (hmax, wmax) a batch of these patches is padded to."""
    return (pow2(max(p.h for p in patches), canvas),
            pow2(max(p.w for p in patches), canvas))
