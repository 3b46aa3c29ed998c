"""Program counter: milliseconds of one decode worker an image spends in
libjpeg's scanline loop (``jpeg_read_scanlines`` to the last row: entropy
decode, the DCT-prescaled IDCT, colour conversion) — ``jpeg_scan_s`` on the
window's ``loader/decode`` spans / their images. A PART of
``input.decode_jpeg_ms``, the one stage that read over half of an image on the
chip machine (PR 35); what is left of that stage is the header and
``jpeg_start_decompress``. A program without the counter reads nothing."""

from benchmark.trace import producer


def read(obs, trace):
    return producer.jpeg_scan_ms(obs)
