"""CAT reproduction: customized transformer accelerator framework in JAX."""
