"""On-chip benchmark of the Distributed-GAN system (see bench/README.md)."""
