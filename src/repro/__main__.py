"""``python -m repro`` — dispatching CLI (see repro.api.cli).

Multi-host quickstart: ``python -m repro serve --listen HOST:PORT``
starts a cluster leader; ``python -m repro join HOST:PORT`` joins it as
a worker from any machine with this package installed (the experiment
spec travels over the wire — see repro.cluster.hostlink).
"""
import sys

if __name__ == "__main__":
    # Importing repro.api pulls in jax, after which the device topology
    # and the compile-cache directory are frozen — set both first
    # (repro.launch._xla_env is jax-free).
    from repro.launch._xla_env import (force_host_device_count,
                                       use_compile_cache)
    use_compile_cache()
    argv = sys.argv[1:]
    if argv and argv[0] == "dryrun":
        # the dry-run's 512 forced host devices
        force_host_device_count()
    from repro.api.cli import main
    sys.exit(main(argv))
