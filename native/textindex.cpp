// Full-text inverted index (reference: engine/index/textindex C++ —
// FullTextIndex.cpp tokenize + posting lists, exposed to Go via cgo
// textbuilder_linux_amd64.go:17-20 AddDocument/RetrievePostingList).
//
// Tokenization (reference SimpleGramTokenizer, FullTextIndex.cpp:19-40
// split table): ASCII alnum runs, lowercased, length >= 2, PLUS one gram
// per multi-byte UTF-8 character — CJK log text indexes per character,
// so non-ASCII search works. Postings are
// per-token sorted vectors of doc ids. C ABI handle-based for ctypes.

#include <cctype>
#include <cstdint>
#include <cstring>
#include <string>
#include <unordered_map>
#include <vector>

namespace {

struct TextIndex {
  std::unordered_map<std::string, std::vector<int64_t>> postings;
  int64_t docs = 0;
};

inline int utf8_seq_len(unsigned char c) {
  // lead-byte length table (reference splitTable): continuation or
  // invalid lead bytes report 1 and are skipped without emitting
  if (c < 0xC0) return 1;
  if (c < 0xE0) return 2;
  if (c < 0xF0) return 3;
  if (c < 0xF8) return 4;
  return 1;
}

void tokenize(const char* text, int64_t len,
              std::vector<std::string>* out) {
  std::string cur;
  int64_t i = 0;
  while (i < len) {
    unsigned char c = static_cast<unsigned char>(text[i]);
    if (c < 0x80) {
      if (std::isalnum(c)) {
        cur.push_back(static_cast<char>(std::tolower(c)));
        ++i;
        continue;
      }
      if (cur.size() >= 2) out->push_back(cur);
      cur.clear();
      ++i;
      continue;
    }
    if (cur.size() >= 2) out->push_back(cur);
    cur.clear();
    int n = utf8_seq_len(c);
    if (i + n > len) break;  // truncated trailing sequence
    if (c >= 0xC0) out->emplace_back(text + i, n);  // one char = one gram
    i += n;  // stray continuation bytes skip silently
  }
  if (cur.size() >= 2) out->push_back(cur);
}

}  // namespace

extern "C" {

void* ogt_text_index_new() { return new TextIndex(); }

void ogt_text_index_free(void* h) { delete static_cast<TextIndex*>(h); }

// Add one document; tokens are deduplicated per document.
void ogt_text_index_add(void* h, int64_t doc_id, const char* text,
                        int64_t len) {
  auto* idx = static_cast<TextIndex*>(h);
  std::vector<std::string> toks;
  tokenize(text, len, &toks);
  idx->docs++;
  for (const auto& t : toks) {
    auto& post = idx->postings[t];
    if (post.empty() || post.back() != doc_id) post.push_back(doc_id);
  }
}

// Number of docs matching the token; fills out up to cap ids.
int64_t ogt_text_index_search(void* h, const char* token, int64_t len,
                              int64_t* out, int64_t cap) {
  auto* idx = static_cast<TextIndex*>(h);
  std::string t;
  for (int64_t i = 0; i < len; ++i) {
    t.push_back(static_cast<char>(
        std::tolower(static_cast<unsigned char>(token[i]))));
  }
  auto it = idx->postings.find(t);
  if (it == idx->postings.end()) return 0;
  int64_t n = static_cast<int64_t>(it->second.size());
  int64_t copy = n < cap ? n : cap;
  std::memcpy(out, it->second.data(), static_cast<size_t>(copy) * 8);
  return n;
}

int64_t ogt_text_index_tokens(void* h) {
  return static_cast<int64_t>(static_cast<TextIndex*>(h)->postings.size());
}

// Standalone tokenizer used for match() row filters: writes token
// boundaries (start, end pairs) into out; returns token count.
int64_t ogt_tokenize(const char* text, int64_t len, int32_t* out,
                     int64_t cap_pairs) {
  int64_t count = 0;
  int64_t start = -1;
  auto emit = [&](int64_t s, int64_t e) {
    if (count < cap_pairs) {
      out[count * 2] = static_cast<int32_t>(s);
      out[count * 2 + 1] = static_cast<int32_t>(e);
    }
    count++;
  };
  int64_t i = 0;
  while (i < len) {
    unsigned char c = static_cast<unsigned char>(text[i]);
    if (c < 0x80) {
      bool alnum = std::isalnum(c);
      if (alnum && start < 0) start = i;
      if (!alnum && start >= 0) {
        if (i - start >= 2) emit(start, i);
        start = -1;
      }
      ++i;
      continue;
    }
    if (start >= 0) {
      if (i - start >= 2) emit(start, i);
      start = -1;
    }
    int n = utf8_seq_len(c);
    if (i + n > len) break;
    if (c >= 0xC0) emit(i, i + n);  // one UTF-8 char = one gram
    i += n;
  }
  if (start >= 0 && len - start >= 2) emit(start, len);
  return count;
}

}  // extern "C"
