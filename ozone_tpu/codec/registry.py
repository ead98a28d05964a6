"""Codec registry with priority ordering and fallback.

Capability mirror of the reference's CodecRegistry (erasurecode
CodecRegistry.java:55-97: ServiceLoader-discovered factories, native-first
ordering) and CodecUtil.createRawEncoderWithFallback (rawcoder/util/
CodecUtil.java:55-82): backends are tried in priority order and the first
one that instantiates wins, so the TPU coder is "just another factory" next
to the numpy reference coder, selectable/overridable by name. The fallback
stops at the device: off the CPU platform a failing jax factory raises
instead of handing back a host coder.
"""

from __future__ import annotations

import logging
from typing import Callable, Optional

from ozone_tpu.codec.api import CoderOptions, RawErasureDecoder, RawErasureEncoder

log = logging.getLogger(__name__)

EncoderFactory = Callable[[CoderOptions], RawErasureEncoder]
DecoderFactory = Callable[[CoderOptions], RawErasureDecoder]

#: Codec families _register_defaults always provides.  CoderOptions.parse
#: validates against known_families() below, which must NOT instantiate
#: the registry (that would eagerly import the jax backend inside every
#: host-only tool that merely parses a replication string).
_DEFAULT_FAMILIES = ("dummy", "lrc", "rs", "xor")


def known_families() -> tuple[str, ...]:
    """Codec family names a CoderOptions string may use, sorted.  Reads
    the live registry when one exists (so test-registered codecs parse),
    else the default family list — without triggering backend imports."""
    reg = CodecRegistry._instance
    if reg is None:
        return _DEFAULT_FAMILIES
    return tuple(sorted(set(_DEFAULT_FAMILIES) | set(reg._factories)))


class _Factory:
    def __init__(self, name: str, priority: int, make_encoder, make_decoder):
        self.name = name
        self.priority = priority
        self.make_encoder = make_encoder
        self.make_decoder = make_decoder


class CodecRegistry:
    """codec name -> ordered list of backend factories."""

    _instance: Optional["CodecRegistry"] = None

    def __init__(self):
        self._factories: dict[str, list[_Factory]] = {}

    @classmethod
    def instance(cls) -> "CodecRegistry":
        if cls._instance is None:
            cls._instance = cls()
            cls._instance._register_defaults()
        return cls._instance

    def register(
        self,
        codec: str,
        backend: str,
        priority: int,
        make_encoder: EncoderFactory,
        make_decoder: DecoderFactory,
    ) -> None:
        """Higher priority is tried first (native/TPU-first ordering,
        reference CodecRegistry.java:92-97)."""
        lst = self._factories.setdefault(codec, [])
        lst.append(_Factory(backend, priority, make_encoder, make_decoder))
        lst.sort(key=lambda f: -f.priority)

    def backends(self, codec: str) -> list[str]:
        return [f.name for f in self._factories.get(codec, [])]

    def _register_defaults(self) -> None:
        from ozone_tpu.codec import numpy_coder

        self.register(
            "rs", "numpy", 10, numpy_coder.NumpyRSEncoder, numpy_coder.NumpyRSDecoder
        )
        self.register(
            "xor",
            "numpy",
            10,
            numpy_coder.NumpyXOREncoder,
            numpy_coder.NumpyXORDecoder,
        )
        self.register(
            "dummy", "numpy", 10, numpy_coder.DummyEncoder, numpy_coder.DummyDecoder
        )
        self.register(
            "lrc",
            "numpy",
            10,
            numpy_coder.NumpyLRCEncoder,
            numpy_coder.NumpyLRCDecoder,
        )
        # C++ backend (ISA-L-class nibble-shuffle kernels): preferred over
        # numpy, below the TPU backend — mirrors the reference's
        # native-first ordering (CodecRegistry.java:92-97)
        # (no toolchain: load() is None and the backend is left out; a
        # toolchain that fails to build raises NativeBuildError)
        from ozone_tpu import native as _native

        if _native.load() is not None:
            from ozone_tpu.codec import cpp_coder

            self.register(
                "rs", "cpp", 50, cpp_coder.CppRSEncoder,
                cpp_coder.CppRSDecoder,
            )
        # TPU backend registers lazily: importing jax is deliberately deferred
        # so host-only tools never pay for it.
        try:
            from ozone_tpu.codec import jax_coder

            self.register(
                "rs", "jax", 100, jax_coder.JaxRSEncoder, jax_coder.JaxRSDecoder
            )
            self.register(
                "xor", "jax", 100, jax_coder.JaxXOREncoder, jax_coder.JaxXORDecoder
            )
        except Exception as e:  # pragma: no cover - jax is present in CI
            log.warning("jax codec backend unavailable: %s", e)

    def _create(self, options: CoderOptions, what: str, backend: Optional[str]):
        factories = self._factories.get(options.codec)
        if not factories:
            raise ValueError(f"no coder registered for codec {options.codec!r}")
        if backend is not None:
            factories = [f for f in factories if f.name == backend]
            if not factories:
                raise ValueError(
                    f"backend {backend!r} not registered for {options.codec!r}"
                )
        errors = []
        for f in factories:
            try:
                maker = f.make_encoder if what == "encoder" else f.make_decoder
                return maker(options)
            except Exception as e:  # fall through to next backend
                if f.name == "jax":
                    import jax

                    # only a platform NAMED cpu may hand the device
                    # coder's work to a host coder; anywhere else a
                    # failing device coder is the error (and a backend
                    # that failed to initialise raises again right here)
                    if jax.default_backend() != "cpu":
                        raise
                errors.append(f"{f.name}: {e}")
                log.warning(
                    "codec backend %s failed for %s, falling back: %s",
                    f.name,
                    options,
                    e,
                )
        raise RuntimeError(
            f"all backends failed for {options.codec} {what}: {'; '.join(errors)}"
        )

    def create_encoder(
        self, options: CoderOptions, backend: Optional[str] = None
    ) -> RawErasureEncoder:
        return self._create(options, "encoder", backend)

    def create_decoder(
        self, options: CoderOptions, backend: Optional[str] = None
    ) -> RawErasureDecoder:
        return self._create(options, "decoder", backend)


def create_encoder(
    options: CoderOptions, backend: Optional[str] = None
) -> RawErasureEncoder:
    return CodecRegistry.instance().create_encoder(options, backend)


def create_decoder(
    options: CoderOptions, backend: Optional[str] = None
) -> RawErasureDecoder:
    return CodecRegistry.instance().create_decoder(options, backend)
