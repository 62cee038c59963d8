"""Plain reference of the action mapping (paper sec. II-C-1): the actor's
unit action in [0, 1] per knob, mapped to the knob's value.

  discrete          floor(a (max - min) + min + 0.5), clipped
  log2_int          powers of two from min to max, uniform in log2
  choice and        the value list indexed by floor(a (n - 1) + 0.5)
  categorical
  boolean           a >= 0.5

Every knob is quantized, so a value is known by its index. The knobs come
from the configuration file (its ``knobs`` list).
"""

from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np


class Knob:
    def __init__(self, spec: dict):
        self.name = spec["name"]
        self.kind = spec["kind"]
        if self.kind == "discrete":
            lo, hi = int(spec["minimum"]), int(spec["maximum"])
            self.values = [float(v) for v in range(lo, hi + 1)]
        elif self.kind == "log2_int":
            e_lo = int(math.log2(spec["minimum"]))
            e_hi = int(math.log2(spec["maximum"]))
            self.values = [float(2 ** e) for e in range(e_lo, e_hi + 1)]
        elif self.kind == "boolean":
            self.values = [0.0, 1.0]
        else:
            self.values = [float(v) for v in spec["values"]]
        self.card = len(self.values)
        pow2 = all(v > 0 and v.is_integer() and (int(v) & (int(v) - 1)) == 0
                   for v in self.values)
        self.log2 = ([float(int(v).bit_length() - 1) for v in self.values]
                     if pow2 else None)
        self.default_index = self.values.index(float(spec["default"]))

    def index(self, a):
        """Quantization index of unit coordinate(s) ``a`` (float32)."""
        if self.kind == "boolean":
            return (a >= 0.5).astype(jnp.float32)
        n = self.card - 1
        return jnp.clip(jnp.floor(a * n + 0.5), 0, n)

    def index_host(self, a: float) -> int:
        """The same in float64 on the host (the final recommendation)."""
        a = min(max(float(a), 0.0), 1.0)
        if self.kind == "boolean":
            return int(a >= 0.5)
        n = self.card - 1
        return int(min(max(math.floor(a * n + 0.5), 0), n))

    def decode(self, idx):
        """{"value", "log2"} of float32 index(es)."""
        i = idx.astype(jnp.int32)
        out = {"value": jnp.asarray(self.values, jnp.float32)[i]}
        if self.log2 is not None:
            out["log2"] = jnp.asarray(self.log2, jnp.float32)[i]
        return out

    def cell(self, idx):
        """[lo, hi): the unit coordinates that quantize to ``idx``."""
        if self.kind == "boolean":
            return (jnp.where(idx > 0, 0.5, -jnp.inf),
                    jnp.where(idx > 0, jnp.inf, 0.5))
        n = self.card - 1
        lo = jnp.where(idx > 0, (idx - 0.5) / n, -jnp.inf)
        hi = jnp.where(idx < n, (idx + 0.5) / n, jnp.inf)
        return lo, hi


class Space:
    def __init__(self, knob_specs: list):
        self.knobs = [Knob(s) for s in knob_specs]
        self.names = tuple(k.name for k in self.knobs)
        self.dim = len(self.knobs)

    def indices(self, action):
        return jnp.stack([k.index(action[j])
                          for j, k in enumerate(self.knobs)])

    def decode(self, idx):
        """Decoded knobs by name, and the vector of decoded values."""
        dec = {k.name: k.decode(idx[j]) for j, k in enumerate(self.knobs)}
        values = jnp.stack([dec[n]["value"] for n in self.names])
        return dec, values

    def gap(self, action, idx):
        """Per knob: how far ``action`` lies outside the unit cell of
        ``idx`` (0 where it quantizes to ``idx``)."""
        out = []
        for j, k in enumerate(self.knobs):
            lo, hi = k.cell(idx[j])
            a = action[j]
            out.append(jnp.maximum(jnp.maximum(lo - a, a - hi), 0.0))
        return jnp.stack(out)

    def default_indices(self) -> np.ndarray:
        return np.array([k.default_index for k in self.knobs], np.float32)

    def value_index(self, name: str, value) -> int:
        k = self.knobs[self.names.index(name)]
        return k.values.index(float(value))

    def config_indices(self, config: dict) -> np.ndarray:
        return np.array([self.value_index(n, config[n]) for n in self.names],
                        np.float32)
