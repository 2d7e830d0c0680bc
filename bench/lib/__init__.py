"""The harness: deployment, load generation, serving, trace and checks."""
