"""The fullest expert's rows over the mean, from the `TokensPerExpert`
the step fetches: median over the window's steps (1.0 is perfect balance;
the grouped products' time follows the sum, a sharded layout's the max)."""


def read(obs):
    return obs.get("expert_load_max_over_mean")
