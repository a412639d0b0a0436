"""Builders: from a configuration file to the system under test.

A configuration file names its builders by role (``"builders": {"train":
"gpt_train", "serve": "gpt_serve"}``); the traffic kind says which role it
drives. A builder module has one function, ``build(env, plan)``, which goes
through the program's public entry points (``deepspeed_tpu.initialize``,
``init_inference``, ``serving.build_serving``) and returns the system the
kind drives, with an ``info`` dict of what the readers need (FLOPs per
token, bytes per step, kernel shapes).
"""
