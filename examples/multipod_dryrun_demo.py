"""Multi-pod dry-run demo: lower + compile the FedGaLore train step for one
assigned architecture on the production meshes (256-chip pod and 2×256
multi-pod) and print the memory / cost / collective analysis.

    PYTHONPATH=src python examples/multipod_dryrun_demo.py [arch]
"""
import sys

from repro.launch import dryrun


def main():
    dryrun.fake_host_devices()
    arch = sys.argv[1] if len(sys.argv) > 1 else "granite-moe-1b-a400m"
    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import TrainSpec

    for multi in (False, True):
        mesh = make_production_mesh(multi_pod=multi)
        print(f"== {arch} train_4k on mesh {dict(mesh.shape)} ==")
        dryrun.analyze_combination(arch, "train_4k", mesh,
                                   TrainSpec(rank=64))


if __name__ == "__main__":
    main()
