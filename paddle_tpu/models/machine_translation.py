"""Seq2seq NMT with bidirectional LSTM encoder + attention decoder
(reference benchmark/fluid/models/machine_translation.py:30-180).

TPU-first: encoder uses the fused dynamic_lstm (lax.scan) forward+reverse;
the decoder's per-step attention (the reference's DynamicRNN +
sequence_expand/sequence_softmax dance) is expressed with the same sequence
ops — LoD ragged batches are packed into SeqTensor (data + lengths) so the
whole graph stays statically shaped for XLA.
"""

import numpy as np

import paddle_tpu as fluid


def bi_lstm_encoder(input_seq, gate_size):
    input_forward_proj = fluid.layers.fc(
        input=input_seq, size=gate_size * 4, act=None, bias_attr=False)
    forward, _ = fluid.layers.dynamic_lstm(
        input=input_forward_proj, size=gate_size * 4, use_peepholes=False)
    input_reversed_proj = fluid.layers.fc(
        input=input_seq, size=gate_size * 4, act=None, bias_attr=False)
    reversed_, _ = fluid.layers.dynamic_lstm(
        input=input_reversed_proj, size=gate_size * 4, is_reverse=True,
        use_peepholes=False)
    return forward, reversed_


def seq_to_seq_net(embedding_dim, encoder_size, decoder_size,
                   source_dict_dim, target_dict_dim,
                   max_source_len=32, max_target_len=32):
    """max_{source,target}_len are STATIC scan bounds for the decoder (they
    size the padded [B,T,*] buffers XLA compiles). They are enforced, not
    advisory: attention_lstm_decoder raises on any batch whose sequences
    exceed the cap (ops/rnn_ops.py _check_cap), so real data longer than
    the default 32 must pass larger caps here rather than being silently
    truncated."""
    src_word_idx = fluid.layers.data(
        name="source_sequence", shape=[1], dtype="int64", lod_level=1)
    src_embedding = fluid.layers.embedding(
        input=src_word_idx, size=[source_dict_dim, embedding_dim],
        dtype="float32")

    src_forward, src_reversed = bi_lstm_encoder(
        input_seq=src_embedding, gate_size=encoder_size)
    encoded_vector = fluid.layers.concat(
        input=[src_forward, src_reversed], axis=1)
    encoded_proj = fluid.layers.fc(
        input=encoded_vector, size=decoder_size, bias_attr=False)

    backward_first = fluid.layers.sequence_pool(
        input=src_reversed, pool_type="first")
    decoder_boot = fluid.layers.fc(
        input=backward_first, size=decoder_size, bias_attr=False, act="tanh")

    # decoder: teacher-forced LSTM over the target sequence; per-step
    # content attention over the encoder states
    trg_word_idx = fluid.layers.data(
        name="target_sequence", shape=[1], dtype="int64", lod_level=1)
    trg_embedding = fluid.layers.embedding(
        input=trg_word_idx, size=[target_dict_dim, embedding_dim],
        dtype="float32")

    # static scan bounds: wmt14 sequences are <= ~17 tokens with <s>/<e>;
    # without these the kernel falls back to scanning ntokens (sum over the
    # batch) masked steps — correct but ~batch_size times more work.
    # Over-cap batches raise inside the op (no silent truncation).
    prediction = fluid.layers.attention_lstm_decoder(
        target_embedding=trg_embedding,
        encoder_vec=encoded_vector,
        encoder_proj=encoded_proj,
        decoder_boot=decoder_boot,
        decoder_size=decoder_size,
        target_dict_dim=target_dict_dim,
        max_target_len=max_target_len, max_source_len=max_source_len)

    label = fluid.layers.data(
        name="label_sequence", shape=[1], dtype="int64", lod_level=1)
    cost = fluid.layers.cross_entropy(input=prediction, label=label)
    avg_cost = fluid.layers.mean(cost)
    return avg_cost, prediction


def beam_decode(exe, train_prog, src_lod_tensor, beam_size=4, max_len=16,
                start_id=0, end_id=1, scope=None):
    """Beam-search inference over a trained seq_to_seq_net
    (reference python/paddle/fluid/tests/book/test_machine_translation.py:1
    decode(): a While loop of DynamicRNN step + beam_search ops; here the
    host drives the loop — each step is one XLA computation of
    attention_lstm_step + beam_search on dense [B*beam_size] rows).

    Returns (sentences, scores): lists of per-beam token-id lists /
    accumulated log-prob floats, src-major beam-minor."""
    import paddle_tpu as fluid
    from paddle_tpu.core.lod_tensor import LoDTensor

    # -- locate decoder wiring in the train program (robust to layer
    #    auto-naming: the op's own input names are the source of truth)
    dec_op = next(op for b in train_prog.blocks for op in b.ops
                  if op.type == "attention_lstm_decoder")
    evec_n = dec_op.input("EncoderVec")[0]
    eproj_n = dec_op.input("EncoderProj")[0]
    boot_n = dec_op.input("DecoderBoot")[0]
    weight_slots = ["WAttState", "WAttScore", "WStep", "BStep", "WOut",
                    "BOut"]
    weight_names = {s: dec_op.input(s)[0] for s in weight_slots}
    table_n = next(
        op for op in train_prog.global_block().ops
        if op.type == "lookup_table"
        and op.input("Ids")[0] == "target_sequence").input("W")[0]

    # -- run the encoder once (test-mode clone, DCE keeps only the encoder)
    infer_prog = train_prog.clone(for_test=True)
    evec, eproj, boot = exe.run(
        infer_prog, feed={"source_sequence": src_lod_tensor},
        fetch_list=[evec_n, eproj_n, boot_n], return_numpy=False,
        scope=scope)

    def to_padded(lt):
        data = np.asarray(lt.numpy())
        offs = lt.last_level_offsets()
        lens = [b - a for a, b in zip(offs, offs[1:])]
        Ts = max(lens)
        B = len(lens)
        o = np.zeros((B, Ts) + data.shape[1:], data.dtype)
        m = np.zeros((B, Ts), "float32")
        for i, (a, b) in enumerate(zip(offs, offs[1:])):
            o[i, : b - a] = data[a:b]
            m[i, : b - a] = 1.0
        return o, m

    evec_p, src_mask = to_padded(evec)
    eproj_p, _ = to_padded(eproj)
    boot = np.asarray(boot.numpy() if hasattr(boot, "numpy") else boot)
    B, Ts, He = evec_p.shape
    D = boot.shape[-1]
    K = beam_size
    rep = lambda a: np.repeat(a, K, axis=0)
    evec_b, eproj_b, mask_b = rep(evec_p), rep(eproj_p), rep(src_mask)

    table = np.asarray(fluid.fetch_var(table_n, scope=scope))
    E, V = table.shape[1], table.shape[0]

    # -- one-step program (weights pulled from the shared scope by name)
    step_prog = fluid.Program()
    with fluid.program_guard(step_prog, fluid.Program()):
        pe = fluid.layers.data(name="prev_emb", shape=[E], dtype="float32")
        ph = fluid.layers.data(name="prev_h", shape=[D], dtype="float32")
        pc = fluid.layers.data(name="prev_c", shape=[D], dtype="float32")
        blk = step_prog.global_block()
        # encoder tensors are loop-invariant: persistable scope vars, set
        # once below — NOT per-step feeds
        ev = blk.create_var(name="beam_evec", shape=[-1, Ts, He],
                            dtype="float32", persistable=True)
        ej = blk.create_var(name="beam_eproj", shape=[-1, Ts, D],
                            dtype="float32", persistable=True)
        sm = blk.create_var(name="beam_smask", shape=[-1, Ts],
                            dtype="float32", persistable=True)
        for s, n in weight_names.items():
            v = train_prog.global_block().vars[n]
            blk.create_var(name=n, shape=v.shape, dtype=v.dtype,
                           persistable=True)
        h_o = blk.create_var(name="step_h", dtype="float32")
        c_o = blk.create_var(name="step_c", dtype="float32")
        lp_o = blk.create_var(name="step_logprobs", dtype="float32")
        blk.append_op(
            type="attention_lstm_step",
            inputs={"PrevEmb": [pe.name], "PrevH": [ph.name],
                    "PrevC": [pc.name], "EncoderVec": [ev.name],
                    "EncoderProj": [ej.name], "SrcMask": [sm.name],
                    **{s: [n] for s, n in weight_names.items()}},
            outputs={"H": [h_o.name], "C": [c_o.name],
                     "LogProbs": [lp_o.name]},
            attrs={})

    # -- beam-step program (ids omitted: candidate id = vocab column)
    beam_prog = fluid.Program()
    with fluid.program_guard(beam_prog, fluid.Program()):
        pi = fluid.layers.data(name="pre_ids", shape=[1], dtype="int64")
        ps = fluid.layers.data(name="pre_scores", shape=[1],
                               dtype="float32")
        cs = fluid.layers.data(name="cand_scores", shape=[V],
                               dtype="float32")
        si, ss, par = fluid.layers.beam_search(
            pi, None, cs, beam_size=K, end_id=end_id, pre_scores=ps,
            return_parents=True)

    pre_ids = np.full((B * K, 1), -1, dtype="int64")
    pre_ids[::K, 0] = start_id
    pre_scores = np.zeros((B * K, 1), dtype="float32")
    h = rep(boot).astype("float32")
    c = np.zeros((B * K, D), dtype="float32")

    # device-resident loop invariants (fed once, read as state every step)
    sc_obj = scope or fluid.global_scope()
    for n, v in (("beam_evec", evec_b), ("beam_eproj", eproj_b),
                 ("beam_smask", mask_b)):
        sc_obj.var(n)
        sc_obj.set_var(n, v.astype("float32"))

    step_ids, step_scores, step_parents = [], [], []
    for _ in range(max_len):
        emb = table[np.clip(pre_ids[:, 0], 0, V - 1)].astype("float32")
        lp, h_new, c_new = exe.run(
            step_prog,
            feed={"prev_emb": emb, "prev_h": h, "prev_c": c},
            fetch_list=["step_logprobs", "step_h", "step_c"], scope=scope)
        cand_scores = pre_scores + np.asarray(lp, "float32")
        sel, sc, par_i = exe.run(
            beam_prog,
            feed={"pre_ids": pre_ids, "pre_scores": pre_scores,
                  "cand_scores": cand_scores},
            fetch_list=[si, ss, par], scope=scope)
        sel = np.asarray(sel, "int64")
        par_i = np.asarray(par_i, "int64")
        step_ids.append(sel)
        step_scores.append(np.asarray(sc, "float32"))
        step_parents.append(par_i)
        # beams follow their parents' recurrent state
        h = np.asarray(h_new)[par_i[:, 0]]
        c = np.asarray(c_new)[par_i[:, 0]]
        pre_ids, pre_scores = sel, np.asarray(sc, "float32")
        if (pre_ids[:, 0] == end_id).all():
            break

    decode_prog = fluid.Program()
    T = len(step_ids)
    with fluid.program_guard(decode_prog, fluid.Program()):
        iv = fluid.layers.data(name="ids", shape=[B * K, 1], dtype="int64")
        sv = fluid.layers.data(name="sc", shape=[B * K, 1], dtype="float32")
        pv = fluid.layers.data(name="par", shape=[B * K, 1], dtype="int64")
        si_v, ss_v = fluid.layers.beam_search_decode(
            iv, sv, parents=pv, end_id=end_id)
        ids_lt, sc_lt = exe.run(
            decode_prog,
            feed={"ids": np.stack(step_ids), "sc": np.stack(step_scores),
                  "par": np.stack(step_parents)},
            fetch_list=[si_v, ss_v], return_numpy=False, scope=scope)

    offs = ids_lt.last_level_offsets()
    toks = np.asarray(ids_lt.numpy()).reshape(-1)
    scs = np.asarray(sc_lt.numpy()).reshape(-1)
    sentences, scores = [], []
    for a, b in zip(offs, offs[1:]):
        sentences.append(toks[a:b].tolist())
        scores.append(float(scs[b - 1]) if b > a else 0.0)
    return sentences, scores


def lodtensor_to_ndarray(lod_tensor):
    import numpy as np
    return np.asarray(lod_tensor.numpy()), lod_tensor.lod()


def get_model(args):
    embedding_dim = 512
    encoder_size = 512
    decoder_size = 512
    dict_size = 30000

    avg_cost, feeding_list = seq_to_seq_net(
        embedding_dim, encoder_size, decoder_size, dict_size, dict_size)

    inference_program = fluid.default_main_program().clone(for_test=True)
    optimizer = fluid.optimizer.Adam(
        learning_rate=getattr(args, "learning_rate", 2e-4))

    train_reader = fluid.batch(
        fluid.reader.shuffle(
            fluid.dataset.wmt14.train(dict_size), buf_size=1000),
        batch_size=args.batch_size)
    test_reader = fluid.batch(
        fluid.dataset.wmt14.test(dict_size), batch_size=args.batch_size)

    return avg_cost, inference_program, optimizer, train_reader, \
        test_reader, None
