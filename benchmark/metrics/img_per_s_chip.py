"""Valid images trained per second per chip over whole epochs that start
after warm-up: images per epoch / median interval between the epoch
boundaries the harness stamped on its own clock / chips.

Also read under the name ``fed_img_per_s_chip`` where the host pipeline feeds
the step. (ISSUE 22 asked for the median start-to-start interval of the
``step`` spans there. On the chip that estimator is bimodal: dispatch is
asynchronous and the device prefetch hands over two steps at once, so the
intervals of one 4-step epoch are 1.8 s, 0.4 s, 0 and 0, and their median
flips between runs — 3 460 and 237 000 images/s for a cell whose epochs say
1 600, PR 22.)"""

import statistics


def read(obs, trace):
    times = [t for t, _ in obs["epoch_marks"][obs["warmup_epochs"] - 1:]]
    gaps = [b - a for a, b in zip(times, times[1:])]
    if not gaps:
        return None
    images = obs["steps_per_epoch"] * obs["global_batch"]
    return images / statistics.median(gaps) / obs["chips"]
