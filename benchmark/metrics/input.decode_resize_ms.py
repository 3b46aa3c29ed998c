"""Program counter: milliseconds of one decode worker an image spends in the
float32 antialiased resize (``resize_rgb`` with its two ``make_kernel`` calls)
— the ``resize`` entry of ``stage_s`` on the window's ``loader/decode`` spans
(nanoseconds by stage kept in native/decode.cpp, where the work happens) /
their images. The four stages sum to ``thread_busy_s`` an image; a program
without the counters reads nothing."""

from benchmark.trace import producer


def read(obs, trace):
    return producer.stage_ms(obs, "resize")
