"""Process start to the first measured step: imports, dataset, build,
compile or cache load, warm-up epochs."""


def read(obs, trace):
    return obs["window_start"] - obs["t_start"]
