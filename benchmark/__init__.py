"""The benchmark of photon-tpu: harness, traffic, references and reducers."""
