"""Wire codec for the cluster transport layer.

Messages crossing a :class:`~repro.transport.bus.MessageBus` carry
arbitrary Python payloads — region values (numpy / jax arrays), stage
instances, placement metadata.  The codec turns a payload into bytes
and back through a small *codec registry*:

* **arrays** — numpy (and jax, via ``__array__``) arrays are encoded as
  ``(dtype, shape, raw bytes)`` so the receiving side reconstructs them
  without a pickle round-trip and large payloads stay a single
  contiguous buffer inside the msgpack frame;
* **anything else msgpack cannot express** (dataclasses, sets,
  StageInstance graphs) falls back to pickle, wrapped so it still
  travels inside the same frame.

msgpack is preferred (compact, zero-copy ``bin`` fields); when the
module is absent the codec degrades to pure pickle framing — same API,
same tests, slower wire format.  Sequences decode as tuples
(``use_list=False``) so region keys like ``("op", 42)`` survive the
round trip unchanged.
"""

from __future__ import annotations

import pickle
from dataclasses import dataclass
from typing import Any, Callable, Optional

try:  # optional dependency: degrade to pickle framing if absent
    import msgpack
except ModuleNotFoundError:  # pragma: no cover - container has msgpack
    msgpack = None

import numpy as np

__all__ = ["Codec", "WireCodec", "default_codec"]

_ND = "__nd__"
_PKL = "__pkl__"
_EXT = "__ext__"


@dataclass(frozen=True)
class Codec:
    """One pluggable entry of the codec registry.

    ``matches`` decides whether this codec handles a value; ``encode``
    must return a msgpack-representable dict tagged with ``tag``;
    ``decode`` inverts it.  Registered codecs are tried in order,
    before the pickle fallback.
    """

    tag: str
    matches: Callable[[Any], bool]
    encode: Callable[[Any], dict]
    decode: Callable[[dict], Any]


def _is_arraylike(value: Any) -> bool:
    return isinstance(value, np.ndarray) or (
        hasattr(value, "__array__") and hasattr(value, "dtype")
        and hasattr(value, "shape") and not np.isscalar(value)
    )


def _encode_array(value: Any) -> dict:
    arr = np.ascontiguousarray(np.asarray(value))
    return {
        "d": arr.dtype.str,
        "s": list(arr.shape),
        "b": arr.tobytes(),
    }


def _decode_array(obj: dict) -> np.ndarray:
    return np.frombuffer(obj["b"], dtype=np.dtype(obj["d"])).reshape(
        tuple(obj["s"])
    ).copy()


#: Arrays first (numpy and jax both satisfy ``__array__``); order matters.
_ARRAY_CODEC = Codec("nd", _is_arraylike, _encode_array, _decode_array)


class WireCodec:
    """Encode/decode whole message frames (lists of message tuples)."""

    def __init__(self, codecs: Optional[list[Codec]] = None):
        self.codecs: list[Codec] = list(codecs) if codecs else [_ARRAY_CODEC]
        # Traffic counters (tests read these).
        self.encoded_bytes = 0
        self.decoded_bytes = 0
        self.pickle_fallbacks = 0

    def register(self, codec: Codec) -> None:
        self.codecs.insert(0, codec)

    # -- msgpack hooks -----------------------------------------------------

    def _default(self, obj: Any) -> Any:
        for codec in self.codecs:
            if codec.matches(obj):
                body = codec.encode(obj)
                body[_EXT] = codec.tag
                return body
        self.pickle_fallbacks += 1
        return {_PKL: pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)}

    def _object_hook(self, obj: dict) -> Any:
        tag = obj.get(_EXT)
        if tag is not None:
            for codec in self.codecs:
                if codec.tag == tag:
                    return codec.decode(obj)
        if _PKL in obj:
            return pickle.loads(obj[_PKL])
        return obj

    # -- framing -----------------------------------------------------------

    def encode(self, obj: Any) -> bytes:
        if msgpack is None:  # pure-pickle degradation
            data = pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)
        else:
            data = msgpack.packb(obj, default=self._default, use_bin_type=True)
        self.encoded_bytes += len(data)
        return data

    def decode(self, data: bytes) -> Any:
        self.decoded_bytes += len(data)
        if msgpack is None:
            return pickle.loads(data)
        return msgpack.unpackb(
            data,
            object_hook=self._object_hook,
            use_list=False,
            strict_map_key=False,
            raw=False,
        )


def default_codec() -> WireCodec:
    """Fresh codec with the built-in array handler registered."""
    return WireCodec()
