"""Shuffle/spill buffer compression codecs.

Reference: `TableCompressionCodec` SPI + nvcomp LZ4
(TableCompressionCodec.scala:41,137, NvcompLZ4CompressionCodec.scala:25).
Here LZ4 is the native C++ block codec (native/lz4.cpp — the nvcomp
analog on host staging buffers) and zstd rides the bundled python
binding.  Selected by ``spark.rapids.shuffle.compression.codec``.
"""
from __future__ import annotations

__all__ = ["Codec", "get_codec"]


class Codec:
    name = "none"

    def compress(self, data: bytes) -> bytes:
        raise NotImplementedError

    def decompress(self, data: bytes, out_size: int) -> bytes:
        raise NotImplementedError


class Lz4Codec(Codec):
    name = "lz4"

    def compress(self, data: bytes) -> bytes:
        from spark_rapids_tpu.native import lz4_compress
        return lz4_compress(data)

    def decompress(self, data: bytes, out_size: int) -> bytes:
        from spark_rapids_tpu.native import lz4_decompress
        return lz4_decompress(data, out_size)


class ZstdCodec(Codec):
    name = "zstd"

    def __init__(self):
        try:
            import zstandard
        except ImportError as e:
            raise RuntimeError(
                "spark.rapids.shuffle.compression.codec=zstd requires the "
                "'zstandard' package, which is not installed in this "
                "environment; install zstandard or pick codec 'lz4' or "
                "'none'") from e
        # a zstandard (de)compressor is not thread-safe and one codec
        # serves every partition thread: a context per call
        self._zstd = zstandard

    def compress(self, data: bytes) -> bytes:
        return self._zstd.ZstdCompressor().compress(data)

    def decompress(self, data: bytes, out_size: int) -> bytes:
        out = self._zstd.ZstdDecompressor().decompress(
            data, max_output_size=out_size)
        if len(out) != out_size:
            raise ValueError(
                f"zstd decompression size mismatch ({len(out)} != "
                f"{out_size})")
        return out


def get_codec(name: str) -> Codec | None:
    """None for "none"; raises on unknown codec names."""
    if name in (None, "", "none"):
        return None
    if name == "lz4":
        return Lz4Codec()
    if name == "zstd":
        return ZstdCodec()
    raise ValueError(f"unknown compression codec {name!r}")
