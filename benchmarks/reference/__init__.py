"""Plain references; they import nothing of faabric_tpu."""
