"""The benchmark of torchsnapshot-tpu: see README.md beside this file."""
