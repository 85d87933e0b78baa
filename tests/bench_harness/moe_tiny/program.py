"""The fixture's ``program_config``: the dense builder's ``LlamaConfig``
with the experts of the fixture configuration's OLMoE-style keys."""


def moe_config(config):
    import dataclasses

    from benchmark.worker import llama_config

    dense = llama_config({**config, "trainer": config["program"]})
    return dataclasses.replace(dense, n_experts=config["num_experts"],
                               top_k=config["num_experts_per_tok"])
