// Native scorer for the exported model bundle.
//
// Parity surface: the reference scores through TensorFlow's C++ runtime via
// JNI — Java TensorflowModel.compute feeds shifu_input_0 / fetches
// shifu_output_0 against a SavedModel (TensorflowModel.java:53-94,112-172).
// This scorer gives the same zero-Python batch-scoring capability against
// the framework-native bundle (shifu_tpu_model.json + shifu_tpu_weights.npz
// written by export/saved_model.py): it parses the architecture JSON,
// loads float32 arrays out of the (stored, uncompressed) npz, applies
// ZSCALE normalization, and runs the config-driven DNN forward pass.
//
// Scope: ALL FOUR bundle families (r04 verdict item 4) — plain DNN,
// wide&deep (wide slice + hashed-cross table), multi-task (shared trunk,
// T sigmoid heads), and the embedding-augmented wrapper around any base
// (hashed per-column tables concatenated to the features).  Feature
// hashing reproduces ops/hashing.py bit-for-bit (same multiplicative
// constants over raw float bits), so bucket assignment is identical to
// the jitted model's.  The reference's evaluator is architecture-agnostic
// because it runs the exported graph (TensorflowModel.java:53-94); this
// scorer reaches the same coverage by implementing each family's forward.
//
// Throughput: rows are processed in blocks with an i-outer blocked GEMM
// (each weight row loaded once per block, reused across rows; inner loop
// contiguous over the output dim for vectorization) and threaded across
// row ranges — the per-row GEMV of the v1 scorer re-streamed W per row.
//
// C ABI (ctypes-friendly; see export/native_scorer.py):
//   void* stpu_scorer_load(const char* dir, char* err, long errlen);
//   long  stpu_scorer_num_features(void* h);
//   long  stpu_scorer_num_outputs(void* h);
//   long  stpu_scorer_score(void* h, const float* rows, long n, float* out);
//         (out: n * num_outputs floats, row-major)
//   void  stpu_scorer_free(void* h);

#include <cctype>
#include <charconv>
#include <cmath>
#include <cstdint>
#include <cstring>
#include <fstream>
#include <map>
#include <memory>
#include <string>
#include <thread>
#include <vector>

#if !(defined(__cpp_lib_to_chars) && __cpp_lib_to_chars >= 201611L)
#include <locale.h>
#include <stdlib.h>
#if defined(__APPLE__)
#include <xlocale.h>
#endif
#endif

namespace {

// Locale-independent number parse: a host app embedding this library may
// have set a non-C LC_NUMERIC locale, under which plain strtod stops at the
// '.' and silently misparses every number.  Prefer from_chars; fall back to
// a locale-pinned strtod_l on toolchains without the floating-point
// overload (libc++ before LLVM 20).
inline bool parse_json_number(const char* p, const char* end, double* out,
                              const char** next) {
#if defined(__cpp_lib_to_chars) && __cpp_lib_to_chars >= 201611L
  auto res = std::from_chars(p, end, *out);
  if (res.ec != std::errc() || res.ptr == p) return false;
  *next = res.ptr;
  return true;
#else
  // bound the token (JSON number grammar chars) and NUL-terminate a copy
  const char* q = p;
  while (q < end && (std::isdigit(static_cast<unsigned char>(*q)) ||
                     *q == '+' || *q == '-' || *q == '.' || *q == 'e' ||
                     *q == 'E'))
    ++q;
  std::string tok(p, q);
  static locale_t c_loc = newlocale(LC_ALL_MASK, "C", static_cast<locale_t>(0));
  char* tail = nullptr;
  *out = strtod_l(tok.c_str(), &tail, c_loc);
  if (tail == tok.c_str()) return false;
  *next = p + (tail - tok.c_str());
  return true;
#endif
}

// ---------------------------------------------------------------- JSON ----
// Minimal recursive-descent parser for the known arch-file structure.
struct JValue {
  enum Kind { NUL, BOOL, NUM, STR, ARR, OBJ } kind = NUL;
  bool b = false;
  double num = 0;
  std::string str;
  std::vector<JValue> arr;
  std::map<std::string, JValue> obj;

  const JValue* get(const std::string& key) const {
    if (kind != OBJ) return nullptr;
    auto it = obj.find(key);
    return it == obj.end() ? nullptr : &it->second;
  }
};

struct JParser {
  const char* p;
  const char* end;
  bool ok = true;

  void skip() {
    while (p < end && (*p == ' ' || *p == '\t' || *p == '\n' || *p == '\r'))
      ++p;
  }
  bool lit(const char* s) {
    size_t n = std::strlen(s);
    if (static_cast<size_t>(end - p) < n || std::memcmp(p, s, n) != 0) {
      ok = false;
      return false;
    }
    p += n;
    return true;
  }
  JValue parse() {
    skip();
    JValue v;
    if (p >= end) {
      ok = false;
      return v;
    }
    switch (*p) {
      case '{': {
        v.kind = JValue::OBJ;
        ++p;
        skip();
        if (p < end && *p == '}') {
          ++p;
          return v;
        }
        while (ok) {
          skip();
          JValue key = parse_string();
          skip();
          if (p >= end || *p != ':') {
            ok = false;
            break;
          }
          ++p;
          v.obj[key.str] = parse();
          skip();
          if (p < end && *p == ',') {
            ++p;
            continue;
          }
          if (p < end && *p == '}') {
            ++p;
            break;
          }
          ok = false;
        }
        return v;
      }
      case '[': {
        v.kind = JValue::ARR;
        ++p;
        skip();
        if (p < end && *p == ']') {
          ++p;
          return v;
        }
        while (ok) {
          v.arr.push_back(parse());
          skip();
          if (p < end && *p == ',') {
            ++p;
            continue;
          }
          if (p < end && *p == ']') {
            ++p;
            break;
          }
          ok = false;
        }
        return v;
      }
      case '"':
        return parse_string();
      case 't':
        v.kind = JValue::BOOL;
        v.b = true;
        lit("true");
        return v;
      case 'f':
        v.kind = JValue::BOOL;
        v.b = false;
        lit("false");
        return v;
      case 'n':
        v.kind = JValue::NUL;
        lit("null");
        return v;
      default: {
        v.kind = JValue::NUM;
        if (!parse_json_number(p, end, &v.num, &p)) ok = false;
        return v;
      }
    }
  }
  JValue parse_string() {
    JValue v;
    v.kind = JValue::STR;
    if (p >= end || *p != '"') {
      ok = false;
      return v;
    }
    ++p;
    while (p < end && *p != '"') {
      char c = *p++;
      if (c == '\\' && p < end) {
        char e = *p++;
        switch (e) {
          case 'n': c = '\n'; break;
          case 't': c = '\t'; break;
          case 'r': c = '\r'; break;
          case 'b': c = '\b'; break;
          case 'f': c = '\f'; break;
          case 'u': {
            // arch files are ASCII; map BMP escapes crudely to '?'
            if (end - p >= 4) p += 4;
            c = '?';
            break;
          }
          default: c = e;
        }
      }
      v.str.push_back(c);
    }
    if (p < end) ++p;  // closing quote
    else ok = false;
    return v;
  }
};

// ----------------------------------------------------------------- NPZ ----
struct Array {
  std::vector<long> shape;
  std::vector<float> data;
};

uint32_t rd32(const uint8_t* p) {
  return static_cast<uint32_t>(p[0]) | (static_cast<uint32_t>(p[1]) << 8) |
         (static_cast<uint32_t>(p[2]) << 16) |
         (static_cast<uint32_t>(p[3]) << 24);
}
uint16_t rd16(const uint8_t* p) {
  return static_cast<uint16_t>(p[0] | (p[1] << 8));
}

bool parse_npy(const uint8_t* buf, size_t len, Array* out, std::string* err) {
  if (len < 10 || std::memcmp(buf, "\x93NUMPY", 6) != 0) {
    *err = "bad npy magic";
    return false;
  }
  int major = buf[6];
  size_t hlen, hoff;
  if (major == 1) {
    hlen = rd16(buf + 8);
    hoff = 10;
  } else {
    if (len < 12) {
      *err = "short npy";
      return false;
    }
    hlen = rd32(buf + 8);
    hoff = 12;
  }
  if (hoff + hlen > len) {
    *err = "short npy header";
    return false;
  }
  std::string header(reinterpret_cast<const char*>(buf + hoff), hlen);
  if (header.find("'<f4'") == std::string::npos) {
    *err = "npz array is not little-endian float32";
    return false;
  }
  if (header.find("'fortran_order': False") == std::string::npos) {
    *err = "fortran-order arrays unsupported";
    return false;
  }
  size_t sp = header.find("'shape':");
  if (sp == std::string::npos) {
    *err = "npy header missing shape";
    return false;
  }
  size_t lp = header.find('(', sp);
  size_t rp = header.find(')', sp);
  if (lp == std::string::npos || rp == std::string::npos) {
    *err = "bad npy shape";
    return false;
  }
  long total = 1;
  const char* q = header.c_str() + lp + 1;
  const char* stop = header.c_str() + rp;
  while (q < stop) {
    char* next = nullptr;
    long d = std::strtol(q, &next, 10);
    if (next == q) break;
    out->shape.push_back(d);
    total *= d;
    q = next;
    while (q < stop && (*q == ',' || *q == ' ')) ++q;
  }
  size_t doff = hoff + hlen;
  if (doff + static_cast<size_t>(total) * 4 > len) {
    *err = "npy data truncated";
    return false;
  }
  out->data.resize(static_cast<size_t>(total));
  std::memcpy(out->data.data(), buf + doff, static_cast<size_t>(total) * 4);
  return true;
}

// Load a .npz (zip) via its central directory; stored (method 0) only —
// np.savez writes uncompressed entries.
bool load_npz(const std::string& path, std::map<std::string, Array>* out,
              std::string* err) {
  std::ifstream f(path, std::ios::binary);
  if (!f) {
    *err = "cannot open " + path;
    return false;
  }
  std::vector<uint8_t> buf((std::istreambuf_iterator<char>(f)),
                           std::istreambuf_iterator<char>());
  if (buf.size() < 22) {
    *err = "npz too small";
    return false;
  }
  // find end-of-central-directory (scan back over a possible zip comment)
  size_t eocd = std::string::npos;
  size_t lo = buf.size() >= (1 << 16) + 22 ? buf.size() - ((1 << 16) + 22) : 0;
  for (size_t i = buf.size() - 22 + 1; i-- > lo;) {
    if (rd32(buf.data() + i) == 0x06054b50) {
      eocd = i;
      break;
    }
  }
  if (eocd == std::string::npos) {
    *err = "zip end-of-central-directory not found";
    return false;
  }
  uint16_t n_entries = rd16(buf.data() + eocd + 10);
  uint32_t cd_off = rd32(buf.data() + eocd + 16);
  size_t p = cd_off;
  for (uint16_t e = 0; e < n_entries; ++e) {
    if (p + 46 > buf.size() || rd32(buf.data() + p) != 0x02014b50) {
      *err = "bad zip central directory";
      return false;
    }
    uint16_t method = rd16(buf.data() + p + 10);
    uint32_t csize = rd32(buf.data() + p + 20);
    uint16_t namelen = rd16(buf.data() + p + 28);
    uint16_t extralen = rd16(buf.data() + p + 30);
    uint16_t commentlen = rd16(buf.data() + p + 32);
    uint32_t lho = rd32(buf.data() + p + 42);
    std::string name(reinterpret_cast<const char*>(buf.data() + p + 46),
                     namelen);
    p += 46 + namelen + extralen + commentlen;
    if (method != 0) {
      *err = "compressed npz unsupported (use np.savez, not savez_compressed)";
      return false;
    }
    // local header: sizes may be zero there; use central-directory values
    if (lho + 30 > buf.size() || rd32(buf.data() + lho) != 0x04034b50) {
      *err = "bad zip local header";
      return false;
    }
    uint16_t lnamelen = rd16(buf.data() + lho + 26);
    uint16_t lextralen = rd16(buf.data() + lho + 28);
    size_t doff = lho + 30 + lnamelen + lextralen;
    if (doff + csize > buf.size()) {
      *err = "zip entry truncated";
      return false;
    }
    if (name.size() >= 4 && name.substr(name.size() - 4) == ".npy") {
      Array arr;
      if (!parse_npy(buf.data() + doff, csize, &arr, err)) {
        *err += " (" + name + ")";
        return false;
      }
      (*out)[name.substr(0, name.size() - 4)] = std::move(arr);
    }
  }
  return true;
}

// --------------------------------------------------------------- model ----
enum class Act { kSigmoid, kTanh, kRelu, kLeakyRelu, kLinear };

Act act_from(const std::string& name) {
  // reference fallback semantics: unknown -> leakyrelu (ssgd_monitor.py:74-88)
  std::string s;
  for (char c : name) s.push_back(static_cast<char>(std::tolower(c)));
  if (s == "sigmoid") return Act::kSigmoid;
  if (s == "tanh") return Act::kTanh;
  if (s == "relu") return Act::kRelu;
  return Act::kLeakyRelu;
}

inline float apply_act(Act a, float x) {
  switch (a) {
    case Act::kSigmoid: return 1.0f / (1.0f + std::exp(-x));
    case Act::kTanh: return std::tanh(x);
    case Act::kRelu: return x > 0 ? x : 0.0f;
    case Act::kLeakyRelu: return x > 0 ? x : 0.01f * x;  // flax default slope
    case Act::kLinear: return x;
  }
  return x;
}

constexpr long kRT = 4;   // rows per register tile
constexpr long kJT = 16;  // output cols per register tile (1 zmm / 2 ymm)

struct Layer {
  Array W;  // (in, out)
  Array b;  // (out,)
  Act act;

  // Tile-packed weights (finalize()): the register-tiled GEMM walks W
  // column-blocks with a 4*out-byte stride, which turns every load into
  // its own cache line (and aliases in L1 for power-of-two widths); the
  // classic fix is packing the B matrix tile-major once so the reduction
  // loop streams contiguously.  Block t holds cols [t*kJT, t*kJT+kJT)
  // as in*kJT consecutive floats (zero-padded past out).
  std::vector<float> Wp;   // (out_pad/kJT, in, kJT)
  std::vector<float> bp;   // (out_pad,) zero-padded bias
  long out_pad = 0;

  void finalize() {
    long in = W.shape[0], outd = W.shape[1];
    out_pad = (outd + kJT - 1) / kJT * kJT;
    Wp.assign(static_cast<size_t>(out_pad / kJT) * in * kJT, 0.0f);
    bp.assign(static_cast<size_t>(out_pad), 0.0f);
    std::memcpy(bp.data(), b.data.data(), static_cast<size_t>(outd) * 4);
    for (long t = 0; t < out_pad / kJT; ++t)
      for (long i = 0; i < in; ++i)
        for (long j = 0; j < kJT; ++j) {
          long col = t * kJT + j;
          if (col < outd)
            Wp[static_cast<size_t>(t) * in * kJT + i * kJT + j] =
                W.data[static_cast<size_t>(i) * outd + col];
        }
  }
};

// ------------------------------------------------------------- hashing ----
// Bit-identical to shifu_tensorflow_tpu/ops/hashing.py: multiplicative
// (Fibonacci) hashing over raw float32 bits, uint32 arithmetic throughout.
constexpr uint32_t kHashMult = 2654435761u;   // HASH_MULT
constexpr uint32_t kHashMult2 = 40503u;       // HASH_MULT2
constexpr uint32_t kColumnSalt = 0x9E3779B9u; // COLUMN_SALT

inline uint32_t float_bits(float v) {
  uint32_t b;
  std::memcpy(&b, &v, 4);
  return b;
}

inline uint32_t hash_mix(uint32_t bits) {
  uint32_t h = bits * kHashMult;
  h ^= h >> 16;
  return h * kHashMult2;
}

// salted_bucket_ids for one value at sliced-column index c
inline long salted_bucket_id(float v, long c, long hash_size) {
  uint32_t salted =
      float_bits(v) ^ (static_cast<uint32_t>(c) * kColumnSalt);
  return static_cast<long>(hash_mix(salted) %
                           static_cast<uint32_t>(hash_size));
}

// crossed_bucket_ids over a row's sliced columns
inline long crossed_bucket_id(const float* vals, long n, long hash_size) {
  uint32_t h = 0;
  for (long c = 0; c < n; ++c) {
    h = (h ^ float_bits(vals[c])) * kHashMult;
    h ^= h >> 13;
  }
  return static_cast<long>(h % static_cast<uint32_t>(hash_size));
}

struct Scorer {
  long num_features = 0;   // raw input width f
  long num_outputs = 1;    // 1 (dnn / wide&deep) or NumTasks (multi-task)
  std::vector<float> means, stds;

  // embedding-augmented wrapper (may wrap any base family)
  std::vector<long> embed_idx;  // positions in the feature vector
  Array embed_table;            // (hash, dim)
  long embed_hash = 0, embed_dim = 0;

  // base family
  enum class Family { kDnn, kWideDeep, kMultiTask } family = Family::kDnn;
  std::vector<Layer> trunk;  // hidden stack (trunk/ or deep/)
  Layer head;                // shifu_output_0 / deep_logit / task_heads

  // wide&deep extras
  std::vector<long> wide_idx;  // empty = the whole (augmented) input
  Array wide_W;                // (wide_in, 1), no bias
  Array cross_table;           // (cross_hash, 1); empty = no cross
  long cross_hash = 0;

  long base_input_dim() const {
    return num_features +
           static_cast<long>(embed_idx.size()) * embed_dim;
  }
};

std::string read_file(const std::string& path, std::string* err) {
  std::ifstream f(path, std::ios::binary);
  if (!f) {
    *err = "cannot open " + path;
    return "";
  }
  return std::string((std::istreambuf_iterator<char>(f)),
                     std::istreambuf_iterator<char>());
}

Scorer* build_scorer(const std::string& dir, std::string* err) {
  std::string arch_text = read_file(dir + "/shifu_tpu_model.json", err);
  if (!err->empty()) return nullptr;
  JParser jp{arch_text.c_str(), arch_text.c_str() + arch_text.size()};
  JValue arch = jp.parse();
  if (!jp.ok) {
    *err = "arch json parse error";
    return nullptr;
  }
  const JValue* params = nullptr;
  if (const JValue* mc = arch.get("model_config"))
    if (const JValue* tr = mc->get("train")) params = tr->get("params");
  if (!params) {
    *err = "arch json missing train.params";
    return nullptr;
  }
  auto str_of = [](const JValue* v, const std::string& d) {
    return v && v->kind == JValue::STR ? v->str : d;
  };
  auto num_of = [](const JValue* v, double d) {
    return v && v->kind == JValue::NUM ? v->num : d;
  };
  auto longs_of = [](const JValue* v) {
    std::vector<long> out;
    if (v && v->kind == JValue::ARR)
      for (const auto& e : v->arr)
        if (e.kind == JValue::NUM) out.push_back(static_cast<long>(e.num));
    return out;
  };
  std::string model_type = str_of(params->get("ModelType"), "dnn");
  if (model_type == "sequence") {
    *err = "native scorer does not cover the sequence family (attention "
           "serving goes through the python/jitted scorer)";
    return nullptr;
  }

  if (model_type == "hybrid_lm") {
    *err = "ModelType='hybrid_lm' is a training-only family: the native "
           "scorer has no path for it";
    return nullptr;
  }

  auto scorer = std::make_unique<Scorer>();
  scorer->num_features =
      static_cast<long>(num_of(arch.get("num_features"), 0));
  if (scorer->num_features <= 0) {
    *err = "arch json missing num_features";
    return nullptr;
  }
  if (const JValue* norm = arch.get("normalization")) {
    const JValue* means = norm->get("means");
    const JValue* stds = norm->get("stds");
    if (means && means->kind == JValue::ARR && stds &&
        stds->kind == JValue::ARR) {
      // score_rows indexes both per feature — a short array would be an
      // out-of-bounds read, so validate like every other loader input
      if (static_cast<long>(means->arr.size()) != scorer->num_features ||
          static_cast<long>(stds->arr.size()) != scorer->num_features) {
        *err = "normalization means/stds length != num_features";
        return nullptr;
      }
      for (const auto& v : means->arr)
        scorer->means.push_back(static_cast<float>(v.num));
      for (const auto& v : stds->arr) {
        float s = static_cast<float>(v.num);
        scorer->stds.push_back(s == 0.0f ? 1.0f : s);
      }
    }
  }

  std::map<std::string, Array> weights;
  if (!load_npz(dir + "/shifu_tpu_weights.npz", &weights, err)) return nullptr;

  // positions of absolute column numbers within the selected feature
  // vector (models/factory.py _column_positions): features arrive already
  // projected to feature_columns order; absent columns are skipped
  std::vector<long> feature_columns =
      longs_of(arch.get("feature_columns"));
  auto positions_of = [&](const std::vector<long>& nums) {
    std::vector<long> out;
    for (long c : nums)
      for (size_t i = 0; i < feature_columns.size(); ++i)
        if (feature_columns[i] == c) {
          out.push_back(static_cast<long>(i));
          break;
        }
    return out;
  };

  // embedding-augmented wrapper: engaged exactly when the factory engages
  // it (EmbeddingColumnNums nonempty, hash size > 0, some column maps)
  std::string prefix;  // weight-path prefix for the base family
  std::vector<long> emb_nums = longs_of(params->get("EmbeddingColumnNums"));
  long emb_hash = static_cast<long>(num_of(params->get("EmbeddingHashSize"), 0));
  if (!emb_nums.empty() && emb_hash > 0) {
    std::vector<long> idx = feature_columns.empty()
        ? [&] {  // no feature_columns: positions 0..C-1 (factory fallback)
            std::vector<long> v(emb_nums.size());
            for (size_t i = 0; i < v.size(); ++i) v[i] = static_cast<long>(i);
            return v;
          }()
        : positions_of(emb_nums);
    if (!idx.empty()) {
      auto tk = weights.find("/hashed_columns/table");
      if (tk == weights.end()) {
        *err = "weights missing /hashed_columns/table";
        return nullptr;
      }
      scorer->embed_table = tk->second;
      if (scorer->embed_table.shape.size() != 2 ||
          scorer->embed_table.shape[0] != emb_hash) {
        *err = "embedding table shape != (EmbeddingHashSize, dim)";
        return nullptr;
      }
      scorer->embed_idx = std::move(idx);
      scorer->embed_hash = emb_hash;
      scorer->embed_dim = scorer->embed_table.shape[1];
      prefix = "/base";
    }
  }

  auto take = [&](const std::string& name, Array* out) {
    auto it = weights.find(prefix + name);
    if (it == weights.end()) {
      *err = "weights missing " + prefix + name;
      return false;
    }
    *out = it->second;
    return true;
  };

  long n_layers = static_cast<long>(num_of(params->get("NumHiddenLayers"), 0));
  const JValue* acts = params->get("ActivationFunc");
  std::string tower = model_type == "wide_deep" ? "/deep/" : "/trunk/";
  for (long i = 0; i < n_layers; ++i) {
    std::string base = tower + "hidden_layer" + std::to_string(i) + "/";
    Layer layer;
    if (!take(base + "kernel", &layer.W) || !take(base + "bias", &layer.b))
      return nullptr;
    layer.act = act_from(
        acts && acts->kind == JValue::ARR &&
                static_cast<size_t>(i) < acts->arr.size()
            ? acts->arr[static_cast<size_t>(i)].str
            : "");
    scorer->trunk.push_back(std::move(layer));
  }

  if (model_type == "wide_deep") {
    scorer->family = Scorer::Family::kWideDeep;
    if (!take("/deep_logit/kernel", &scorer->head.W) ||
        !take("/deep_logit/bias", &scorer->head.b))
      return nullptr;
    scorer->head.act = Act::kSigmoid;  // applied after wide+cross sum
    if (!take("/wide_logit/kernel", &scorer->wide_W)) return nullptr;
    std::vector<long> wide_nums = longs_of(params->get("WideColumnNums"));
    scorer->wide_idx = positions_of(wide_nums);  // empty = whole input
    long cross = static_cast<long>(num_of(params->get("CrossHashSize"), 0));
    // factory gates the cross on WideColumnNums being present
    if (cross > 0 && !wide_nums.empty()) {
      if (!take("/wide_cross/table", &scorer->cross_table)) return nullptr;
      if (scorer->cross_table.shape.size() != 2 ||
          scorer->cross_table.shape[0] != cross ||
          scorer->cross_table.shape[1] != 1) {
        *err = "wide_cross table shape != (CrossHashSize, 1)";
        return nullptr;
      }
      scorer->cross_hash = cross;
    }
    long wide_in = scorer->wide_idx.empty()
                       ? scorer->base_input_dim()
                       : static_cast<long>(scorer->wide_idx.size());
    if (scorer->wide_W.shape.size() != 2 ||
        scorer->wide_W.shape[0] != wide_in ||
        scorer->wide_W.shape[1] != 1) {
      *err = "wide_logit kernel shape mismatch";
      return nullptr;
    }
  } else if (model_type == "multi_task") {
    scorer->family = Scorer::Family::kMultiTask;
    if (!take("/task_heads/kernel", &scorer->head.W) ||
        !take("/task_heads/bias", &scorer->head.b))
      return nullptr;
    scorer->head.act = Act::kSigmoid;
    long tasks = static_cast<long>(num_of(params->get("NumTasks"), 1));
    if (scorer->head.W.shape.size() != 2 ||
        scorer->head.W.shape[1] != tasks) {
      *err = "task_heads kernel width != NumTasks";
      return nullptr;
    }
    scorer->num_outputs = tasks;
  } else {
    scorer->family = Scorer::Family::kDnn;
    if (!take("/shifu_output_0/kernel", &scorer->head.W) ||
        !take("/shifu_output_0/bias", &scorer->head.b))
      return nullptr;
    scorer->head.act = Act::kSigmoid;
  }

  // shape sanity: hidden chain must start at the (augmented) input width
  // and flow into the head
  long in_dim = scorer->base_input_dim();
  for (const auto& l : scorer->trunk) {
    if (l.W.shape.size() != 2 || l.W.shape[0] != in_dim ||
        l.b.shape.size() != 1 || l.b.shape[0] != l.W.shape[1]) {
      *err = "weight shape chain mismatch";
      return nullptr;
    }
    in_dim = l.W.shape[1];
  }
  if (scorer->head.W.shape.size() != 2 || scorer->head.W.shape[0] != in_dim ||
      scorer->head.b.shape.size() != 1 ||
      scorer->head.b.shape[0] != scorer->head.W.shape[1]) {
    *err = "head shape mismatch";
    return nullptr;
  }
  if (scorer->family != Scorer::Family::kMultiTask &&
      scorer->head.W.shape[1] != 1) {
    *err = "output head is not 1-unit";
    return nullptr;
  }
  for (auto& l : scorer->trunk) l.finalize();
  scorer->head.finalize();
  return scorer.release();
}

// Blocked dense: C (R, out) = X (R, in) @ W (in, out) + b, then act.
//
// Register-tiled GEMM over PACKED weights: kRT×kJT accumulators live in
// registers across the whole i (reduction) loop — the naive i-outer/axpy
// form reads and writes the C row from memory on EVERY i step (2 memory
// ops per FMA).  The packed layout (Layer::finalize) makes the per-tile
// reduction stream W contiguously; per i step the full tile loads kJT
// weight floats + kRT x floats for kRT*kJT FMAs, and the compile-time
// tile bounds let the compiler keep the accumulators in ymm/zmm
// registers and emit FMA over the contiguous j dimension.

// one full kRT×kJT tile; wblk = packed block base (in * kJT floats)
void dense_tile_full(const float* X, long in, long outd, const float* wblk,
                     const float* bp, long r0, long j0, float* C) {
  float acc[kRT][kJT];
  for (long r = 0; r < kRT; ++r)
    for (long j = 0; j < kJT; ++j) acc[r][j] = bp[j0 + j];
  const float* x0 = X + r0 * in;
  for (long i = 0; i < in; ++i) {
    const float* w = wblk + i * kJT;
    for (long r = 0; r < kRT; ++r) {
      float xi = x0[r * in + i];
      // g++12 -O3 alone picks 16-byte vectors here (measured 3.7 GFLOP/s);
      // the simd pragma gets the full-width FMA form (65 GFLOP/s)
#pragma omp simd
      for (long j = 0; j < kJT; ++j) acc[r][j] += xi * w[j];
    }
  }
  long Jj = std::min(kJT, outd - j0);  // drop zero-padded cols on store
  for (long r = 0; r < kRT; ++r)
    std::memcpy(C + (r0 + r) * outd + j0, acc[r],
                static_cast<size_t>(Jj) * 4);
}

// row remainder (R % kRT rows), same packed walk
void dense_tile_rows(const float* X, long in, long outd, const float* wblk,
                     const float* bp, long r0, long Rr, long j0, float* C) {
  float acc[kRT][kJT];
  for (long r = 0; r < Rr; ++r)
    for (long j = 0; j < kJT; ++j) acc[r][j] = bp[j0 + j];
  const float* x0 = X + r0 * in;
  for (long i = 0; i < in; ++i) {
    const float* w = wblk + i * kJT;
    for (long r = 0; r < Rr; ++r) {
      float xi = x0[r * in + i];
#pragma omp simd
      for (long j = 0; j < kJT; ++j) acc[r][j] += xi * w[j];
    }
  }
  long Jj = std::min(kJT, outd - j0);
  for (long r = 0; r < Rr; ++r)
    std::memcpy(C + (r0 + r) * outd + j0, acc[r],
                static_cast<size_t>(Jj) * 4);
}

void dense_block(const float* X, long R, const Layer& L, Act act, float* C) {
  long in = L.W.shape[0], outd = L.W.shape[1];
  long Rfull = R - R % kRT;
  for (long t = 0; t < L.out_pad / kJT; ++t) {
    const float* wblk = L.Wp.data() + static_cast<size_t>(t) * in * kJT;
    long j0 = t * kJT;
    for (long r0 = 0; r0 < Rfull; r0 += kRT)
      dense_tile_full(X, in, outd, wblk, L.bp.data(), r0, j0, C);
    if (Rfull < R)
      dense_tile_rows(X, in, outd, wblk, L.bp.data(), Rfull, R - Rfull,
                      j0, C);
  }
  for (long r = 0; r < R; ++r)
    for (long j = 0; j < outd; ++j)
      C[r * outd + j] = apply_act(act, C[r * outd + j]);
}

constexpr long kBlockRows = 64;

void score_rows(const Scorer& s, const float* rows, long n, float* out) {
  long f = s.num_features;
  long D = s.base_input_dim();
  long max_w = D;
  for (const auto& l : s.trunk) max_w = std::max(max_w, l.W.shape[1]);
  max_w = std::max(max_w, s.head.W.shape[1]);
  std::vector<float> xbuf(static_cast<size_t>(kBlockRows) * D);
  std::vector<float> h(static_cast<size_t>(kBlockRows) * max_w);
  std::vector<float> h2(static_cast<size_t>(kBlockRows) * max_w);
  std::vector<float> widebuf;

  for (long r0 = 0; r0 < n; r0 += kBlockRows) {
    long R = std::min(kBlockRows, n - r0);
    // 1. normalize the raw features into the block input buffer
    for (long r = 0; r < R; ++r) {
      const float* src = rows + (r0 + r) * f;
      float* dst = xbuf.data() + r * D;
      if (!s.means.empty())
        for (long j = 0; j < f; ++j)
          dst[j] = (src[j] - s.means[j]) / s.stds[j];
      else
        std::memcpy(dst, src, static_cast<size_t>(f) * 4);
    }
    // 2. embedding wrapper: gather per-column hashed embeddings and
    //    append them to the features (models/factory.EmbeddingAugmented)
    if (s.embed_hash > 0) {
      long C = static_cast<long>(s.embed_idx.size());
      for (long r = 0; r < R; ++r) {
        float* x = xbuf.data() + r * D;
        float* e = x + f;
        for (long c = 0; c < C; ++c) {
          long id = salted_bucket_id(x[s.embed_idx[c]], c, s.embed_hash);
          std::memcpy(e + c * s.embed_dim,
                      s.embed_table.data.data() + id * s.embed_dim,
                      static_cast<size_t>(s.embed_dim) * 4);
        }
      }
    }
    // 3. hidden stack
    const float* cur = xbuf.data();
    long cur_w = D;
    for (const auto& layer : s.trunk) {
      dense_block(cur, R, layer, layer.act, h2.data());
      h.swap(h2);
      cur = h.data();
      cur_w = layer.W.shape[1];
    }
    (void)cur_w;
    // 4. head (+ wide&deep extras), sigmoid applied after summing logits
    long T = s.head.W.shape[1];
    if (s.family == Scorer::Family::kWideDeep) {
      // deep_logit WITHOUT activation yet
      dense_block(cur, R, s.head, Act::kLinear, h2.data());
      for (long r = 0; r < R; ++r) {
        const float* x = xbuf.data() + r * D;
        float logit = h2[r * T];
        // wide linear over the designated slice (or the whole input)
        if (s.wide_idx.empty()) {
          for (long i = 0; i < D; ++i)
            logit += x[i] * s.wide_W.data[static_cast<size_t>(i)];
        } else {
          for (size_t i = 0; i < s.wide_idx.size(); ++i)
            logit += x[s.wide_idx[i]] * s.wide_W.data[i];
        }
        // crossed categorical: joint hash of the wide slice
        if (s.cross_hash > 0) {
          widebuf.resize(s.wide_idx.empty() ? static_cast<size_t>(D)
                                            : s.wide_idx.size());
          if (s.wide_idx.empty())
            std::memcpy(widebuf.data(), x, static_cast<size_t>(D) * 4);
          else
            for (size_t i = 0; i < s.wide_idx.size(); ++i)
              widebuf[i] = x[s.wide_idx[i]];
          long id = crossed_bucket_id(
              widebuf.data(), static_cast<long>(widebuf.size()),
              s.cross_hash);
          logit += s.cross_table.data[static_cast<size_t>(id)];
        }
        out[(r0 + r)] = apply_act(Act::kSigmoid, logit);
      }
    } else {
      dense_block(cur, R, s.head, s.head.act, h2.data());
      for (long r = 0; r < R; ++r)
        std::memcpy(out + (r0 + r) * T, h2.data() + r * T,
                    static_cast<size_t>(T) * 4);
    }
  }
}

void set_err(char* err, long errlen, const std::string& msg) {
  if (err && errlen > 0) {
    std::snprintf(err, static_cast<size_t>(errlen), "%s", msg.c_str());
  }
}

}  // namespace

extern "C" {

void* stpu_scorer_load(const char* model_dir, char* err, long errlen) {
  if (!model_dir) {
    set_err(err, errlen, "null model_dir");
    return nullptr;
  }
  std::string msg;
  Scorer* s = build_scorer(model_dir, &msg);
  if (!s) set_err(err, errlen, msg);
  return s;
}

long stpu_scorer_num_features(void* handle) {
  return handle ? static_cast<Scorer*>(handle)->num_features : -1;
}

long stpu_scorer_num_outputs(void* handle) {
  return handle ? static_cast<Scorer*>(handle)->num_outputs : -1;
}

// rows: n * num_features raw (un-normalized) float32; out: n * num_outputs
// scores, row-major.  Multi-threads across row blocks for large batches.
// Returns n or -1.
long stpu_scorer_score(void* handle, const float* rows, long n, float* out) {
  if (!handle || !rows || !out || n < 0) return -1;
  const Scorer& s = *static_cast<Scorer*>(handle);
  const long kRowsPerThread = 4096;
  int hw = static_cast<int>(std::thread::hardware_concurrency());
  int nt = static_cast<int>(
      std::min<long>(std::max(1, hw), (n + kRowsPerThread - 1) / kRowsPerThread));
  if (nt <= 1) {
    score_rows(s, rows, n, out);
    return n;
  }
  std::vector<std::thread> threads;
  long per = (n + nt - 1) / nt;
  for (int t = 0; t < nt; ++t) {
    long begin = t * per;
    long count = std::min(per, n - begin);
    if (count <= 0) break;
    threads.emplace_back([&s, rows, out, begin, count] {
      score_rows(s, rows + begin * s.num_features, count,
                 out + begin * s.num_outputs);
    });
  }
  for (auto& th : threads) th.join();
  return n;
}

void stpu_scorer_free(void* handle) { delete static_cast<Scorer*>(handle); }

}  // extern "C"
