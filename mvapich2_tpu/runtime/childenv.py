"""Child-process environment construction shared by the launch paths
(launcher, mpispawn agents, MPI_Comm_spawn).

One process owns a chip: a second process that initializes jax on a TPU
host fails or hangs on libtpu's lock. Every process these launch paths
start is host-runtime only, so each gets ``JAX_PLATFORMS=cpu`` forced —
whether or not the parent has touched jax, a rank child can never reach
for the device the parent (or a sibling) may hold."""


def cpu_rank_env(env: dict, explicit: bool = False) -> dict:
    """Finalize a rank child's environment.

    Rank processes run the host runtime only (progress loop, matching,
    channels) and must not grab the accelerator — so ``JAX_PLATFORMS``
    is *forced* to cpu, not defaulted: the launcher's own environment
    often carries the accelerator platform, and inheriting it makes
    every rank fight over the one device.

    Opt-outs, both of which survive into the rank env so nested launch
    paths (mpispawn agents, MPI_Comm_spawn children) keep them:
      * ``MV2T_RANK_PLATFORM=<platform>`` — ranks get that platform;
      * ``explicit=True`` (caller passed JAX_PLATFORMS via env_extra) —
        recorded as ``MV2T_PLATFORM_EXPLICIT=1``.
    """
    if explicit:
        env["MV2T_PLATFORM_EXPLICIT"] = "1"
    explicit = env.get("MV2T_PLATFORM_EXPLICIT") == "1"
    want = env.get("MV2T_RANK_PLATFORM")
    if want:
        env["JAX_PLATFORMS"] = want
    elif not explicit:
        env["JAX_PLATFORMS"] = "cpu"
    return env
