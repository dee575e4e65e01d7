"""``python -m deepspeed_tpu`` — the launcher CLI (reference ``bin/deepspeed``).

Subcommand-free: forwards to the launcher's main (hostfile parse,
include/exclude filters, ssh fan-out, ``--autotune``). ``--report`` prints
the environment report (reference ``bin/ds_report``)."""

import sys


def main():
    if "--report" in sys.argv[1:2]:
        from .env_report import main as report_main

        return report_main()
    if "--elastic" in sys.argv[1:2]:
        # reference bin/ds_elastic: print the elastic batch + valid chip
        # counts for a config
        import json

        from .elasticity import compute_elastic_config

        args = sys.argv[2:]
        if not args:
            print("usage: python -m deepspeed_tpu --elastic CONFIG.json "
                  "[WORLD_SIZE]", file=sys.stderr)
            return 2
        with open(args[0]) as fh:
            cfg = json.load(fh)
        world = int(args[1]) if len(args) > 1 else 0
        out = compute_elastic_config(cfg, world_size=world,
                                     return_microbatch=world > 0)
        if world > 0:
            batch, valid, micro = out
            print(json.dumps({"final_batch_size": batch,
                              "valid_chips": valid, "micro_batch": micro}))
        else:
            batch, valid = out
            print(json.dumps({"final_batch_size": batch,
                              "valid_chips": valid}))
        return 0
    from .launcher.runner import main as runner_main

    return runner_main()


if __name__ == "__main__":
    sys.exit(main())
