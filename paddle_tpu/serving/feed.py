"""The fused step's small feeds as ONE int32 buffer.

Every iteration hands the fused step the scheduler's plan: tokens,
positions, valid, tables and, where the step samples, the five sampling
controls. Each host array that crosses to the device costs a transfer
call of its own, and on a TPU host that call's fixed cost dwarfs the
bytes of arrays this small. `FeedLayout` lays them end to end in one
contiguous int32 buffer, so that the feed crosses in one call: `pack`
on the host, `unpack` inside the jitted step, both reading the same
offsets. Four-byte fields go in as their bits (`view(np.int32)`) and
come back by `lax.bitcast_convert_type`, so a float keeps `inf`, NaN
and negative zero; bools go in as 0/1 and come back by `!= 0`. The
unpacked arrays are the host arrays bit for bit.

The layout is fixed by the server's `(S, C, M)`, which never change, so
the step still has one signature."""

import math

import numpy as np

import jax.numpy as jnp
from jax import lax

__all__ = ["FeedLayout"]


class FeedLayout:
    """Offsets of `fields`, a sequence of (shape, dtype) in the order
    the step takes them, inside one flat int32 buffer of `size`
    elements. A dtype is bool or four bytes wide."""

    def __init__(self, fields):
        self.fields = tuple((tuple(shape), np.dtype(dtype))
                            for shape, dtype in fields)
        bad = [dt for _shape, dt in self.fields
               if dt != np.bool_ and dt.itemsize != 4]
        if bad:
            raise ValueError(f"a packed feed holds bools and four-byte "
                             f"fields, not {bad}")
        self.offsets, size = [], 0
        for shape, _dtype in self.fields:
            self.offsets.append(size)
            size += math.prod(shape)
        self.size = size

    def pack(self, arrays):
        """The host arrays, in field order, as one int32 buffer."""
        buf = np.empty(self.size, np.int32)
        for (shape, dtype), o, a in zip(self.fields, self.offsets, arrays,
                                        strict=True):
            if a.shape != shape or a.dtype != dtype:
                raise ValueError(f"feed {a.dtype}{list(a.shape)} where "
                                 f"the layout has {dtype}{list(shape)}")
            flat = a.reshape(-1)
            buf[o:o + flat.size] = (flat if dtype == np.bool_
                                    else flat.view(np.int32))
        return buf

    def unpack(self, packed):
        """Inside the step: the fields back from the buffer, with their
        shapes and dtypes (static slices; no data moves)."""
        out = []
        for (shape, dtype), o in zip(self.fields, self.offsets):
            x = lax.slice(packed, (o,), (o + math.prod(shape),)
                          ).reshape(shape)
            if dtype == np.bool_:
                x = x != 0
            elif dtype != np.int32:
                x = lax.bitcast_convert_type(x, jnp.dtype(dtype))
            out.append(x)
        return tuple(out)
